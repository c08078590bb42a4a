"""Host time of one engine iteration: each ``engine.step`` span in the
window less the ``engine.launch`` spans inside it (the jitted calls through
their fence), as a mean per iteration. Prints the mean split by the step's
direct child spans and the least share of a step its children cover."""
import sys

import scopes


def read(r):
    rows = scopes.step_split(scopes.engine_spans(r), r.trace.window)
    if not rows:
        return None
    n = len(rows)
    step = sum(d for d, _, _ in rows) * 1e-6 / n
    launch = sum(x for _, x, _ in rows) * 1e-6 / n
    split = {}
    for _, _, children in rows:
        for name, ns in children.items():
            split[name] = split.get(name, 0.0) + ns
    cover = min((sum(c.values()) / d for d, _, c in rows if d), default=0.0)
    err = sys.stderr
    print(f"engine_host_ms: {n} steps, a step {step:.3f} ms, launches "
          f"{launch:.3f} ms of it; children cover at least "
          f"{100 * cover:.2f}% of a step", file=err)
    print("  ms a step by child: " + ", ".join(
        f"{k} {v * 1e-6 / n:.3f}" for k, v in
        sorted(split.items(), key=lambda kv: -kv[1])), file=err)
    return step - launch
