"""Chip benchmark entry point.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Reads the cell from ``BENCHMARK.json`` and finds everything else by name:
the configuration ``chipbench/configs/<config>.json`` and its policy file,
the traffic mix ``chipbench/traffic/<traffic>.json``, the per-layer readers
``chipbench/metrics/<metric>.py``, the correctness limits
``chipbench/limits/<cell>.json`` and the peaks ``chipbench/peaks.json``.

It loads, warms up every shape the window uses, measures for ``--seconds``
and prints one JSON line last on standard output. ``--trace 0`` reports the
cell's end-to-end metrics; ``--trace 1`` runs the window under the profiler
and reports the per-layer metrics. After the window the served tokens,
and the logits of the launches that follow it, are held to the plain
reference (``reference.py``); each compared number is printed beside its
limit, last on standard error and last in the JSON line.

It exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import common  # noqa: E402


def fail(msg: str, code: int = 2):
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


class Compiles:
    """Counts compile requests and sums compile seconds (backend compiles
    and reads from the persistent cache)."""

    def __init__(self, jax):
        self.requests = 0
        self.seconds = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1

    def _duration(self, name, secs, **_):
        if name in ("/jax/core/compile/backend_compile_duration",
                    "/jax/compilation_cache/cache_retrieval_time_sec"):
            self.seconds += secs


class Run:
    """One run of one cell: its files, its seed, and the set-up clock."""

    def __init__(self, args, bench, cell, raw=None, policy_text=None,
                 mix=None, lim=None, compute="float32"):
        self.args, self.bench, self.cell = args, bench, cell
        self.seed = args.seed
        self.raw = raw or common.config_file(cell["config"])
        self.cfg = common.model_config(self.raw)
        if policy_text is None:
            with open(os.path.join(common.ROOT, self.raw["policy"])) as f:
                policy_text = f.read()
        self.policy_text = policy_text
        self.policy = json.loads(policy_text)
        self.mix = mix or common.read_json(
            os.path.join("chipbench", "traffic", f"{cell['traffic']}.json"))
        self.limits = limits(cell["name"]) if lim is None else lim
        # the program's compute dtype: float32 as the configuration
        # states; the control switches the program's own bfloat16 path on
        self.compute = compute
        self.phases = {}

    @contextlib.contextmanager
    def phase(self, name):
        t0 = time.perf_counter()
        yield
        self.phases[name] = time.perf_counter() - t0


def load_reader(name):
    path = os.path.join(common.BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench, cell, trace):
    """The cell's end-to-end metrics, or its per-layer ones."""
    name = cell["name"]

    def applies(m):
        if "workloads" in m:
            return name in m["workloads"]
        return True

    if not trace:
        return [m for m in bench["end_to_end"] if applies(m)]
    e2e = {m["name"] for m in bench["end_to_end"] if applies(m)}
    return [m for m in bench["per_layer"]
            if applies(m) and m["moves"] in e2e]


def peaks(kind):
    table = common.read_json(os.path.join("chipbench", "peaks.json"))
    if kind not in table:
        fail(f"no peaks for device kind {kind!r} in chipbench/peaks.json")
    return table[kind]


def limits(cell_name):
    path = os.path.join(common.BENCH, "limits", f"{cell_name}.json")
    if not os.path.exists(path):
        return {}
    with open(path) as f:
        return json.load(f)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    args = ap.parse_args(argv)

    bench_path = os.path.join(common.ROOT, "BENCHMARK.json")
    if not os.path.exists(bench_path):
        fail("no BENCHMARK.json at the root of the checkout")
    bench = common.read_json(bench_path)
    cells = {c["name"]: c for c in bench["workloads"]}
    if args.workload not in cells:
        fail(f"unknown workload {args.workload!r}; known: {sorted(cells)}")
    cell = cells[args.workload]

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX's platform is {dev.platform!r}")
    if len(devices) < cell["chips"]:
        fail(f"the cell needs {cell['chips']} chips, JAX finds "
             f"{len(devices)}")
    result = measure(Run(args, bench, cell), jax, dev, devices)
    emit(result)


def measure(run, jax, dev, devices, controls=(), pk=None):
    """Set up, measure and check one run; returns the result dict."""
    compiles = Compiles(jax)
    pk = pk or peaks(dev.device_kind)
    kind = run.mix["kind"]
    if kind == "serve":
        import serve_cell as impl
    else:
        fail(f"unknown traffic kind {kind!r}")
    cell = impl.Cell(run)
    cell.setup()
    setup_s = time.perf_counter() - T_START
    print(f"set-up {setup_s:.3f} s: " + ", ".join(
        f"{k} {v:.3f} s" for k, v in run.phases.items())
        + f"; compile and cache reads {compiles.seconds:.3f} s in "
        f"{compiles.requests} requests", file=sys.stderr, flush=True)

    trace_dir = os.path.join(common.BENCH, "out", "trace", run.cell["name"])
    before = compiles.requests
    span = None
    if run.args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        span = jax.profiler.TraceAnnotation
        with span("bench.window"):
            cell.window(run.args.seconds, span)
        jax.profiler.stop_trace()
    else:
        cell.window(run.args.seconds)
    in_window = compiles.requests - before
    stats = dev.memory_stats() or {}
    in_use, peak = stats.get("bytes_in_use", 0), stats.get("peak_bytes_in_use", 0)
    late = cell.finish()
    print(f"compiles inside the window: {in_window}", file=sys.stderr,
          flush=True)

    read = cell.readings()
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    metrics, breakdown = {}, None
    wanted = cell_metrics(run.bench, run.cell, run.args.trace)
    if not run.args.trace:
        e2e = {"setup_s": setup_s, "hbm_in_use_gib": in_use / 2**30}
        e2e.update(read["e2e"])
        for m in wanted:
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    else:
        import reduce_trace as tr

        red = tr.reduce(tr.find(trace_dir))
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        breakdown = {"device_ops": [list(x) for x in red.top_ops()],
                     "idle_gaps": [list(x) for x in red.top_gaps()]}
        reading = impl.Reading(run, cell, red, pk, read["e2e"])
        for m in wanted:
            v = load_reader(m["name"])(reading)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        shutil.rmtree(trace_dir, ignore_errors=True)

    cell.stretch()
    sample = cell.check_sample()
    cell.release()
    gc.collect()
    checks, ctl = impl.check(run, sample, run.limits, controls)
    correct = impl.held(checks) and not late
    out = {"correct": correct, "attempted": read["attempted"],
           "failed": late, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    if controls:
        out["controls"] = ctl
    return out


def emit(out):
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
