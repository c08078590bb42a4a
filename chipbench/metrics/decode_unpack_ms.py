"""Device time of the weight-code unpack in one decode launch: the window's
ops of the decode program under an ``unpack`` named scope
(``runtime/packing.PackedLinear.unpack``), tied to the scope through the
compiled program's text (``scopes.py``), over the window's decode launches.
Prints the split by weight bits and by projection, the count of ops the
text does not name, the ten longest ops with their scopes, and the Pallas
calls by instruction name."""
import sys

import kernels
import scopes


def read(r):
    table = scopes.op_names(scopes.decode_text(r.cell))
    ops = [o for o in r.trace.ops if kernels.in_program(o, "jit_decode")]
    tot, paths, unresolved = {}, {}, 0
    for o in ops:
        path = scopes.scope_of(o, table)
        if path is None:
            unresolved += 1
            continue
        key = o.name.split(" = ", 1)[0]
        tot[key] = tot.get(key, 0.0) + o.dur
        paths[key] = path
    err = sys.stderr
    print(f"decode_unpack_ms: {len(ops)} decode ops in the window, "
          f"{unresolved} not in the compiled text", file=err)
    for key in sorted(tot, key=lambda k: -tot[k])[:10]:
        print(f"  {key} {tot[key] * 1e-9:.4f} s  {paths[key]}", file=err)
    # a Pallas call's instruction takes its kernel's name
    calls = {}
    for o in ops:
        if kernels.custom(o):
            name = o.name.split(" = ", 1)[0].lstrip("%").rsplit(".", 1)[0]
            calls[name] = calls.get(name, 0) + 1
    print("  Pallas calls in the window by instruction name: " + ", ".join(
        f"{k} {v}" for k, v in sorted(calls.items())), file=err)
    launches = r.hist["engine.decode_step_ms"][1]
    unpack = {k: v for k, v in tot.items() if scopes.under(paths[k], "unpack")}
    if not unpack or not launches:
        return None
    by_bits, by_proj = {}, {}
    for key, ns in unpack.items():
        at = scopes.site(paths[key])
        bits = r.policy["w_bits"].get(".".join(at)) if at else None
        proj = at[1] if at else "outside a site"
        by_bits[bits] = by_bits.get(bits, 0.0) + ns
        by_proj[proj] = by_proj.get(proj, 0.0) + ns
    for title, split in (("bits", by_bits), ("projection", by_proj)):
        print(f"  unpack ms a launch by {title}: " + ", ".join(
            f"{k} {v * 1e-6 / launches:.3f}" for k, v in
            sorted(split.items(), key=lambda kv: -kv[1])), file=err)
    return sum(unpack.values()) * 1e-6 / launches
