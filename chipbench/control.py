"""Readings for the correctness limits of a serving cell, many seeds in one
process.

For each seed, a run of the cell as the benchmark makes it, ``correct``
decided by the harness at the cell's limits (``chipbench/limits/<cell>.json``).
A seed written ``bf16:<seed>`` runs the control instead: the program's own
bfloat16 compute path switched on, the precision below the float32 that the
configuration states. A plain seed also reads the reference put in the
program's place in bfloat16 and in float8, at the same positions. One JSON
line per seed on standard output.

Usage:  python chipbench/control.py <cell> <seconds> <seed> [bf16:<seed> ...]
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback

import common  # noqa: F401
import run as bench_run

REFERENCE_CONTROLS = ("bfloat16", "float8_e4m3fn")


def main():
    name, seconds = sys.argv[1], float(sys.argv[2])
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit("control: no TPU")
    bench = common.read_json("BENCHMARK.json")
    cell = {c["name"]: c for c in bench["workloads"]}[name]
    for word in sys.argv[3:]:
        compute, _, seed = word.rpartition(":")
        compute = {"": "float32", "bf16": "bfloat16"}[compute]
        args = argparse.Namespace(workload=name, seed=int(seed),
                                  seconds=seconds, trace=0)
        controls = REFERENCE_CONTROLS if compute == "float32" else ()
        try:
            out = bench_run.measure(
                bench_run.Run(args, bench, cell, compute=compute), jax, dev,
                jax.devices(), controls=controls)
        except Exception:  # a control that crashes has failed
            traceback.print_exc()
            print(json.dumps({"seed": int(seed), "compute": compute,
                              "crashed": True}), flush=True)
            continue
        print(json.dumps({"seed": int(seed), "compute": compute,
                          "correct": out["correct"], "checks": out["checks"],
                          "controls": out.get("controls", {}),
                          "metrics": out["metrics"]}), flush=True)


if __name__ == "__main__":
    main()
