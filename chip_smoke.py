"""Bring-up run of the LIMPQ pipeline on one TPU at Qwen3-0.6B width.

One process runs the paper's pipeline through the entry points a user calls,
at the full published width and depth of ``qwen3-0.6b`` with seeded weights:

  1. devices     exits non-zero unless JAX's platform is ``tpu``;
  2. train       importance-mode steps of ``repro.launch.train`` (every
                 uniform-bit pass plus the random pass; training exits on a
                 non-finite loss) saving the learned indicators;
  3. search      ``core.search.search_policy`` over those indicators under the
                 uniform 4-bit BitOps budget, saved as a policy JSON;
  4. serve       ``repro.launch.serve --policy ... --check`` with int8 KV,
                 8 requests at 8 slots, 2048-token prompts (a multiple of the
                 flash block, so prefill takes the Pallas kernel) and 32
                 generated tokens;
                 serve's own gates run (packed bytes, every kernel held to
                 its dequant-fp route op by op, dequant-fp token identity,
                 kernel-route logit drift), and the dispatch counters must
                 show the Pallas matmuls, the fused decode attention and
                 the Pallas flash forward, none interpreted;
  5. serve again, 2 requests sharing half their prompt, 16 generated
                 tokens each, with the paged KV layout and self-speculative
                 decoding, which reaches the paged and verify kernels; the
                 same gates run on the paged layout.

On a TPU the dequant-fp token gate admits near-ties (``serve.tie_bound``):
two equivalent fp graphs round apart there, and a token may trail the
reference's first choice by less than that bound.

Compile seconds, step times and peak device memory are printed as
information. The last line of standard output is the JSON result.

``--chips 4`` runs only the tensor-parallel comparison, at published widths
cut to 4 layers: the packed session on one chip against the same session on
the 1x4 mesh (token identity, and per-shard packed bytes within 5% of
``policy.size_bytes / 4``).

Usage:  python chip_smoke.py [--chips 4]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ARCH = "qwen3-0.6b"
SEED = 0
# importance training: widths and depth are the published ones; batch x
# seq comes from the compiled step's memory_analysis() for one v5e (7.8 GB
# at 2 x 2048, remat on). 2048 tokens take the Pallas flash forward.
TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 3, 2, 2048
# every request is admitted at once (fixed schedule): each engine launch
# costs a fixed unpack of the sub-byte weight codes, so fewer launches keep
# the run inside its time limit
SERVE = ["--arch", ARCH, "--slots", "8", "--prompt-len", "2048", "--gen", "32",
         "--schedule", "fixed", "--seed", str(SEED), "--check"]


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


class Phases:
    """Wall seconds, backend compile seconds and peak device memory per
    phase, printed as each phase ends."""

    def __init__(self, jax, device):
        self.device = device
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def run(self, name, fn, *args):
        t0, c0 = time.perf_counter(), self.compile_s
        print(f"=== phase {name}", flush=True)
        out = fn(*args)
        peak = (self.device.memory_stats() or {}).get("peak_bytes_in_use", 0)
        print(f"=== phase {name} done: {time.perf_counter() - t0:.1f} s wall, "
              f"{self.compile_s - c0:.1f} s backend compile, peak device "
              f"memory {peak / 2**30:.2f} GiB", flush=True)
        return out


def train_and_search(workdir):
    from repro.configs import get_config
    from repro.core import search
    from repro.launch import train
    from repro.models import lm

    ind_path = os.path.join(workdir, "indicators.json")
    print(f"importance training: batch {TRAIN_BATCH} x seq {TRAIN_SEQ}, "
          f"{TRAIN_STEPS} steps")
    train.main(["--arch", ARCH, "--mode", "importance",
                "--steps", str(TRAIN_STEPS), "--batch", str(TRAIN_BATCH),
                "--seq", str(TRAIN_SEQ), "--log-every", "1",
                "--seed", str(SEED), "--save-indicators", ind_path])
    with open(ind_path) as f:
        ind = json.load(f)
    cfg = get_config(ARCH)
    ql = lm.enumerate_qlayers(cfg)
    budget = search.bitops_budget_for_uniform(ql, 4)
    res = search.search_policy(ql, ind, cfg.bits, bitops_budget=budget)
    if res.bitops > budget * (1 + 1e-9):
        fail(f"searched policy breaks its BitOps budget: {res.bitops} > "
             f"{budget}")
    policy_path = os.path.join(workdir, "policy.json")
    res.policy.save(policy_path)
    avg_w, avg_a = res.policy.avg_bits()
    print(f"searched policy: {len(ql)} layers, avg bits w {avg_w:.3f} "
          f"a {avg_a:.3f}, bitops {res.bitops:.4g} <= budget {budget:.4g}, "
          f"{res.size_bytes / 1e6:.2f} MB, {res.solver} solve "
          f"{res.elapsed_s * 1e3:.1f} ms -> {policy_path}")
    return policy_path


def serve_and_check_routes(policy_path, extra):
    from repro.launch import serve

    eng, completions = serve.main(SERVE + ["--policy", policy_path] + extra)
    routes = serve.route_counts(eng.metrics)
    kernels = [r for r in ("dispatch.route.pallas-int8",
                           "dispatch.route.pallas-w4",
                           "dispatch.route.pallas-planes") if routes.get(r)]
    if not kernels:
        fail(f"no Pallas matmul route ran: {routes}")
    if eng.stats.decode_attn_route != "fused" \
            or not routes.get("dispatch.decode_attn.fused"):
        fail(f"decode attention did not take the fused kernel: "
             f"{eng.stats.decode_attn_route} {routes}")
    if "--kv-layout" not in extra and (
            not routes.get("dispatch.flash_fwd.pallas")
            or routes.get("dispatch.flash_fwd.jnp-scan")):
        fail(f"prefill did not take the Pallas flash forward: {routes}")
    if any("interpret" in r for r in routes):
        fail(f"a kernel ran in interpret mode: {routes}")
    if "--speculate" in extra and not eng.stats.spec_rounds:
        fail("speculative serving ran no draft/verify round")
    print(f"routes ok ({eng.ecfg.kv_layout} KV): {', '.join(kernels)}, "
          f"fused decode attention, {len(completions)} requests")


def tensor_parallel(jax):
    from repro.runtime import sharded_smoke

    # published widths; depth cut to 4 layers, which keeps every layer
    # kind's megatron split and a fraction of the 28-layer compile time
    preset = dict(arch=ARCH, smoke=False, depth=4, slots=4, prompt_len=128,
                  gen=16, n_requests=4, arrive_every=1)
    ref, sharded = sharded_smoke.run_sharded_vs_single(preset, (1, 4))
    c = sharded_smoke.sharded_counters(ref, sharded)
    sess, axes = sharded["session"], sharded["axes"]
    ideal = sess.policy.size_bytes(sess.qlayers, per_shard=axes.tp_size)
    print(f"1x4 mesh: dp={axes.dp_size} tp={axes.tp_size} | per-shard packed "
          f"bytes {c['sharded_per_shard_bytes']} vs size_bytes/4 "
          f"{ideal:.0f} | tokens identical: {c['sharded_token_identical']}")
    if not c["sharded_token_identical"]:
        bad = [r for r in ref if ref[r] != sharded["tokens"][r]]
        fail(f"1x4 mesh session diverged from the one-chip session on "
             f"rids {bad}")
    if c["sharded_per_shard_bytes"] > ideal * 1.05:
        fail(f"per-shard packed bytes {c['sharded_per_shard_bytes']} exceed "
             f"size_bytes/4 = {ideal:.0f} by more than 5%")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the 1x4 tensor-parallel comparison")
    args = ap.parse_args()

    root = os.path.dirname(os.path.abspath(__file__))
    if not os.path.isdir(os.path.join(root, "src", "repro")):
        fail(f"no repro package under {root}/src: run from a checkout")
    sys.path.insert(0, os.path.join(root, "src"))

    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"devices: {devices}")
    print(f"platform {dev.platform}, kind {dev.device_kind!r}, "
          f"count {len(devices)}")
    if dev.platform != "tpu":
        fail(f"no TPU: JAX's platform is {dev.platform!r}")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} devices, "
             f"found {len(devices)}")

    from repro.launch import compile_cache

    print(f"compile cache: {compile_cache.enable()}")
    phases = Phases(jax, dev)
    if args.chips == 4:
        phases.run("tensor-parallel 1x4", tensor_parallel, jax)
    else:
        workdir = os.path.join(root, "chiprun_out", "chip_smoke")
        os.makedirs(workdir, exist_ok=True)
        policy = phases.run("train + search", train_and_search, workdir)
        phases.run("serve ring", serve_and_check_routes, policy,
                   ["--requests", "8"])
        # 16 tokens: this pass builds four engines (speculative, plain,
        # dequant-fp, reference) and the reference scorer within the time
        # limit, into a cache of 17 whole pages
        phases.run("serve paged + speculate", serve_and_check_routes, policy,
                   ["--requests", "2", "--gen", "16", "--kv-layout", "paged",
                    "--page-size", "128", "--cache-len", "2176",
                    "--speculate", "4"])
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
