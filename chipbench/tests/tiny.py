"""A run of the harness at a size that a CPU test can hold.

It skips the harness's look for a chip: ``measure`` is called with the CPU
device, a small configuration of the benchmark's own kind, a policy that
uses every searched width, and a small closed-loop mix.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import common  # noqa: E402,F401

PROJ = ("wq", "wk", "wv", "wo", "mlp_wi", "mlp_wg", "mlp_wo")
PEAKS = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9,
         "hbm_bytes": 16e9}


SMALL = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=2, vocab_size=256)
# the smallest size at which a control precision flips served tokens
WIDER = dict(hidden_size=256, intermediate_size=512, num_hidden_layers=4,
             num_attention_heads=16, num_key_value_heads=8, vocab_size=8192)


def raw(tied=True, size=SMALL):
    return dict(size, name="tiny", head_dim=16, rope_theta=10000.0,
                rms_norm_eps=1e-6, tie_word_embeddings=tied,
                hidden_act="silu", qk_norm=tied)


def policy(layers=2):
    bits = (2, 3, 4, 5, 6)
    w, a = {}, {}
    for i in range(layers):
        for j, p in enumerate(PROJ):
            w[f"L{i:03d}.{p}"] = bits[(i + j) % 5]
            a[f"L{i:03d}.{p}"] = bits[(2 * i + j + 2) % 5]
    return json.dumps({"w_bits": w, "a_bits": a, "meta": {}})


def mix(ttft=False):
    return {"kind": "serve", "loop": "closed", "clients": 4, "slots": 4,
            "cache_len": 64, "bucket_min": 8, "staggered": not ttft,
            "ttft": ttft,
            "prompt": {"median": 12, "sigma": 0.6, "min": 8, "max": 32},
            "output": {"median": 8, "sigma": 0.6, "min": 4, "max": 16},
            "check_requests": 3, "check_rows_requests": 3}


# At this size on the CPU the sound program serves the reference's own
# tokens and logits (gap 0, logits within 1e-6: the dequant-fp routes are
# the fake-quant graph); the chip's limits come from chip readings
# (chipbench/limits/).
LIMIT = 0.5
DEV_LIMIT = 1e-3


def run(seconds=3.0, seed=2**33 + 7, tied=True, limit=LIMIT, trace=0,
        controls=(), size=SMALL, compute="float32"):
    import jax

    import run as bench_run

    bench = common.read_json(os.path.join(common.ROOT, "BENCHMARK.json"))
    cell = {"name": "tiny.decode", "config": "tiny", "traffic": "tiny",
            "chips": 1}
    # the tiny cell stands in for every cell a metric lists
    bench = dict(bench, workloads=[cell], **{
        kind: [dict(m, workloads=[cell["name"]]) if "workloads" in m else m
               for m in bench[kind]] for kind in ("end_to_end", "per_layer")})
    args = argparse.Namespace(workload=cell["name"], seed=seed,
                              seconds=seconds, trace=trace)
    r = bench_run.Run(args, bench, cell, raw=raw(tied, size),
                      policy_text=policy(size["num_hidden_layers"]),
                      mix=mix(), lim={"gap_max": {"limit": limit},
                                      "dev_ms": {"limit": DEV_LIMIT}},
                      compute=compute)
    dev = jax.devices()[0]
    return bench_run.measure(r, jax, dev, jax.devices(), controls=controls,
                             pk=PEAKS)


if __name__ == "__main__":
    print(json.dumps(run(controls=("bfloat16",))))
