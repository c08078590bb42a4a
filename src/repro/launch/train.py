"""Training driver (CPU-runnable end to end; mesh-ready by construction).

Three modes mirroring the paper's pipeline (§4.1):

  importance  — joint n+1-pass indicator training (paper §3.4)
  qat         — finetune with a searched policy active (or uniform bits)
  fp          — full-precision baseline

Fault tolerance: atomic async checkpoints every --ckpt-every steps,
auto-resume from the latest step, straggler watchdog, deterministic
skip-to-step data (no replay needed after restart).

Example:
  python -m repro.launch.train --arch limpq-demo --mode importance --steps 50
  python -m repro.launch.train --arch limpq-demo --mode qat \
      --policy experiments/policy.json --steps 200
"""
from __future__ import annotations

import argparse
import json
import math
import time

import jax
import jax.numpy as jnp

from repro import optim, training
from repro.checkpoint import CheckpointManager, StepWatchdog
from repro.configs import get_config, smoke_config
from repro.core import importance as imp
from repro.core.policy import MPQPolicy
from repro.data import SyntheticLM
from repro.dist.axes import NO_AXES
from repro.launch import compile_cache
from repro.models import lm
from repro.models.quant_layers import QuantContext, fp_context


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="limpq-demo")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config of --arch")
    ap.add_argument("--mode", default="qat",
                    choices=["importance", "qat", "fp"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=None)
    ap.add_argument("--policy", default=None,
                    help="MPQPolicy json for qat mode (default: uniform 4b)")
    ap.add_argument("--uniform-bits", type=int, default=4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--no-freeze-backbone", action="store_true")
    ap.add_argument("--save-indicators", default=None)
    args = ap.parse_args(argv)
    compile_cache.enable()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    rng = jax.random.PRNGKey(args.seed)
    params = lm.init_params(rng, cfg)
    data = SyntheticLM(cfg)
    ctx = (fp_context(jnp.float32) if args.mode == "fp"
           else QuantContext.make(cfg.bits, cfg.quant_act_signed,
                                  compute_dtype=jnp.float32))

    # ---- bits -------------------------------------------------------------
    bits = None
    if args.mode == "qat":
        ql = lm.enumerate_qlayers(cfg)
        if args.policy:
            policy = MPQPolicy.load(args.policy)
        else:
            policy = MPQPolicy.uniform(ql, args.uniform_bits)
        bits = lm.bits_from_policy(cfg, policy, ql)

    # ---- optimizer + step ---------------------------------------------------
    if args.mode == "importance":
        lr = args.lr if args.lr is not None else 0.01
        opt = imp.importance_optimizer(
            lr, freeze_backbone=not args.no_freeze_backbone)
        # remat: each pass's backward re-runs its layer forward instead of
        # keeping every layer's fake-quantized weights live — without it a
        # full-width step outgrows one 16 GB chip at batch 1
        step_fn = jax.jit(imp.make_importance_step(cfg, ctx, opt, NO_AXES,
                                                   remat=True),
                          donate_argnums=(0, 1))
    else:
        lr = args.lr if args.lr is not None else 3e-3
        opt = optim.adamw(optim.cosine_warmup(lr, args.steps // 20 + 1,
                                              args.steps),
                          weight_decay=2.5e-5, clip_norm=1.0)
        step_fn = jax.jit(training.make_train_step(cfg, ctx, opt, bits,
                                                   NO_AXES, remat=False))
    opt_state = opt.init(params)

    # ---- checkpoint / resume -----------------------------------------------
    mgr = None
    start = 0
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir, keep_n=3)
        latest = mgr.latest_step()
        if latest is not None:
            params = mgr.restore(latest, params)
            opt_state = mgr.restore_opt(latest, opt_state) \
                if hasattr(mgr, "restore_opt") else opt_state
            start = latest + 1
            print(f"resumed from step {latest}")

    wd = StepWatchdog()
    srng = jax.random.PRNGKey(args.seed + 1)
    t_start = time.time()
    for step in range(start, args.steps):
        batch = {k: jnp.asarray(v)
                 for k, v in data.batch(step, args.batch, args.seq).items()}
        t0 = time.time()
        if args.mode == "importance":
            srng, sub = jax.random.split(srng)
            params, opt_state, m = step_fn(params, opt_state, batch, sub)
            # one loss per uniform-bit pass, then the random pass
            losses = [float(x) for x in m["loss_uniform"]]
            losses.append(float(m["loss_random"]))
            loss = sum(losses[:-1]) / len(losses[:-1])
        else:
            params, opt_state, m = step_fn(params, opt_state, batch)
            losses = [float(m["loss"])]
            loss = losses[0]
        dt = time.time() - t0
        if not all(math.isfinite(x) for x in losses):
            raise SystemExit(f"step {step}: non-finite loss {losses}")
        if wd.observe(dt):
            print(f"[watchdog] step {step} straggled: {dt:.2f}s")
        if step % args.log_every == 0 or step == args.steps - 1:
            per_pass = ""
            if args.mode == "importance":
                per_pass = "  passes [" + " ".join(
                    f"{x:.4f}" for x in losses) + "]"
            print(f"step {step:5d}  loss {loss:.4f}  {dt*1e3:7.1f} ms"
                  + per_pass)
        if mgr and (step + 1) % args.ckpt_every == 0:
            mgr.save(step, params, meta={"arch": cfg.name, "mode": args.mode})
    if mgr:
        mgr.save(args.steps - 1, params,
                 meta={"arch": cfg.name, "mode": args.mode}, blocking=True)

    if args.mode == "importance" and args.save_indicators:
        ql = lm.enumerate_qlayers(cfg)
        ind = imp.extract_indicators(params, cfg, ql)
        with open(args.save_indicators, "w") as f:
            json.dump({k: {"w": v["w"].tolist(), "a": v["a"].tolist()}
                       for k, v in ind.items()}, f, indent=1)
        print(f"indicators -> {args.save_indicators}")
    print(f"total {time.time()-t_start:.1f}s")
    return params


if __name__ == "__main__":
    main()
