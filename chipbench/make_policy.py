"""Make a configuration's bit policy on the chip with the repo's own pipeline.

For each configuration named on the command line: importance (indicator)
training through ``repro.core.importance`` on the program's synthetic corpus,
then ``repro.core.search.search_policy`` under the BitOps budget of a uniform
4-bit network. The batch is the largest of a fixed list whose compiled step
fits the chip by ``memory_analysis()``. The policy, with how it was made in
its ``meta``, is written to ``chipbench/out/policies/<name>.policy.json``;
copy it to ``chipbench/configs/`` to use it.

Usage:  python chipbench/make_policy.py qwen3-0.6b yi-9b
"""
from __future__ import annotations

import json
import os
import sys
import time

import archs
import common

STEPS = 3
SEED = 0
CANDIDATES = ((2, 2048), (1, 2048), (2, 1024), (1, 1024), (1, 512))


def fits(step, args, limit):
    m = step.lower(*args).compile().memory_analysis()
    need = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)
    return need <= 0.9 * limit, need


def make(name, out_dir):
    import jax
    import jax.numpy as jnp

    from repro.core import importance as imp
    from repro.core import search
    from repro.data import SyntheticLM
    from repro.dist.axes import NO_AXES
    from repro.models import lm
    from repro.models.quant_layers import QuantContext

    raw = common.config_file(name)
    cfg = archs.load(raw).model_config(raw)
    dev = jax.devices()[0]
    limit = dev.memory_stats()["bytes_limit"]
    params = jax.jit(lm.init_params, static_argnums=1)(
        jax.random.PRNGKey(SEED), cfg)
    ctx = QuantContext.make(cfg.bits, cfg.quant_act_signed,
                            compute_dtype=jnp.float32)
    opt = imp.importance_optimizer(0.01, freeze_backbone=True)
    step = jax.jit(imp.make_importance_step(cfg, ctx, opt, NO_AXES,
                                            remat=True),
                   donate_argnums=(0, 1))
    opt_state = opt.init(params)
    data = SyntheticLM(cfg)
    rng = jax.random.PRNGKey(SEED + 1)
    chosen, tried = None, []
    for b, s in CANDIDATES:
        batch = {k: jnp.asarray(v) for k, v in data.batch(0, b, s).items()}
        ok, need = fits(step, (params, opt_state, batch, rng), limit)
        tried.append({"batch": b, "seq": s, "bytes": int(need), "fits": ok})
        print(f"{name}: batch {b} x seq {s} needs {need / 2**30:.2f} GiB "
              f"of {limit / 2**30:.2f}: {'fits' if ok else 'does not fit'}",
              flush=True)
        if ok:
            chosen = (b, s)
            break
    if chosen is None:
        sys.exit(f"{name}: no batch fits")
    b, s = chosen
    losses = []
    for i in range(STEPS):
        batch = {k: jnp.asarray(v) for k, v in data.batch(i, b, s).items()}
        rng, sub = jax.random.split(rng)
        t0 = time.perf_counter()
        params, opt_state, m = step(params, opt_state, batch, sub)
        row = [float(x) for x in m["loss_uniform"]] + [float(m["loss_random"])]
        print(f"{name}: step {i} {1e3 * (time.perf_counter() - t0):.1f} ms "
              f"losses {row}", flush=True)
        losses.append(row)
    ql = lm.enumerate_qlayers(cfg)
    ind = imp.extract_indicators(params, cfg, ql)
    budget = search.bitops_budget_for_uniform(ql, 4)
    res = search.search_policy(ql, ind, cfg.bits, bitops_budget=budget)
    avg_w, avg_a = res.policy.avg_bits()
    res.policy.meta.pop("solve_report", None)
    res.policy.meta["made_by"] = {
        "script": "chipbench/make_policy.py",
        "pipeline": "core.importance joint indicator training (every "
                    "uniform-bit pass plus the random pass, backbone "
                    "frozen, SGD lr 0.01), then core.search.search_policy "
                    "(dp) under the BitOps budget of uniform 4 bits",
        "device": f"{dev.platform} {dev.device_kind}",
        "init_seed": SEED, "steps": STEPS, "batch": b, "seq": s,
        "batch_choice": tried, "losses": losses,
        "avg_bits_w": avg_w, "avg_bits_a": avg_a,
        "size_bytes": res.size_bytes}
    path = os.path.join(out_dir, f"{name}.policy.json")
    with open(path, "w") as f:
        f.write(res.policy.to_json())
    print(f"{name}: {len(ql)} layers, avg bits w {avg_w:.3f} a {avg_a:.3f}, "
          f"{res.size_bytes / 1e6:.2f} MB -> {path}", flush=True)
    del params, opt_state


def main():
    import jax

    if jax.devices()[0].platform != "tpu":
        sys.exit("make_policy: no TPU")
    out_dir = os.path.join(common.BENCH, "out", "policies")
    os.makedirs(out_dir, exist_ok=True)
    for name in sys.argv[1:]:
        make(name, out_dir)
    print(json.dumps({"ok": True}))


if __name__ == "__main__":
    main()
