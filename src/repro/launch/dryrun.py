import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

# ^ MUST precede every other import (jax locks device count on first init).
# The dry-run compiles against 512 placeholder CPU devices; pinning the CPU
# keeps it off an attached accelerator, which one process at a time may hold.
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. resolves the arch's partition rules (divisibility fallbacks included),
  3. lowers the production step — QAT ``train_step`` with a mixed-precision
     policy active for train shapes, ``prefill_step`` / ``serve_step`` for
     inference shapes — against ShapeDtypeStruct inputs (no allocation),
  4. compiles, records ``memory_analysis()`` + ``cost_analysis()`` + the
     trip-count-scaled HLO analysis (repro.dist.hlo), and
  5. writes a JSON artifact to experiments/dryrun/ that §Roofline reads.

The policy baked into the dry-run train step cycles bit-widths across
layers — structurally identical to an ILP-searched policy (static
per-layer bank indices) without requiring full-scale indicator training.

Usage:
  python -m repro.launch.dryrun --arch all --shape all --mesh both
  python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k --mesh single
  python -m repro.launch.dryrun --list
  python -m repro.launch.dryrun --importance-cell        # paper-core step
"""
import argparse
import gzip
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro import optim
from repro.configs import get_config, list_archs
from repro.configs.base import SHAPES_BY_NAME, SHAPES, shape_applicable
from repro.core.policy import MPQPolicy
from repro.dist import hlo as hlo_mod
from repro.dist import roofline, sharding
from repro.launch.mesh import make_mesh_by_name
from repro.models import lm
from repro.models.quant_layers import QuantContext
from repro.core import importance as importance_mod

from jax.sharding import PartitionSpec as P


def cyclic_policy(cfg) -> MPQPolicy:
    """Static mixed policy: bits cycle across QLayers (w and a offset)."""
    ql = lm.enumerate_qlayers(cfg)
    bits = cfg.bits
    n = len(bits)
    w = {q.name: int(bits[i % n]) for i, q in enumerate(ql)}
    a = {q.name: int(bits[(i + 2) % n]) for i, q in enumerate(ql)}
    return MPQPolicy(w, a, meta={"kind": "cyclic-dryrun"})


def _named(mesh, spec_tree):
    return sharding.named(mesh, spec_tree)


def build_cell(cfg, shape, mesh, *, step_kind: str, zero_shard: bool = True,
               remat: bool = True, shard_seq="auto"):
    """Returns (jitted_fn, example_args as ShapeDtypeStructs)."""
    axes = sharding.make_axes_for(cfg, mesh, shard_seq=shard_seq)
    ctx = QuantContext.make(cfg.bits, cfg.quant_act_signed)   # bf16 compute
    rng = jax.random.PRNGKey(0)
    params_shape = jax.eval_shape(lambda k: lm.init_params(k, cfg), rng)
    pspecs = sharding.param_specs(cfg, params_shape, axes)
    inputs = lm.input_specs(cfg, shape)
    bspecs = sharding.batch_specs(cfg, inputs, axes)
    bits = lm.bits_from_policy(cfg, cyclic_policy(cfg))

    if step_kind == "train":
        opt = optim.adamw(optim.cosine_warmup(3e-4, 500, 50_000),
                          weight_decay=2.5e-5, clip_norm=1.0)
        opt_shape = jax.eval_shape(opt.init, params_shape)
        zspecs = (sharding.zero_sharded_specs(cfg, params_shape, axes)
                  if zero_shard else pspecs)
        ospecs = type(opt_shape)(P(), zspecs, zspecs)

        def step(params, opt_state, batch):
            (loss, _), grads = jax.value_and_grad(
                lm.loss_fn, has_aux=True)(params, cfg, batch, bits, ctx,
                                          axes, remat)
            updates, opt_state = opt.update(grads, opt_state, params)
            params = optim.apply_updates(params, updates)
            return params, opt_state, loss

        jitted = jax.jit(
            step,
            in_shardings=(_named(mesh, pspecs), _named(mesh, ospecs),
                          _named(mesh, bspecs)),
            out_shardings=(_named(mesh, pspecs), _named(mesh, ospecs), None),
            donate_argnums=(0, 1))
        return jitted, (params_shape, opt_shape, inputs)

    if step_kind == "importance":
        opt = importance_mod.importance_optimizer(0.01, freeze_backbone=True)
        opt_shape = jax.eval_shape(opt.init, params_shape)
        # momentum exists for the trainable indicator banks only
        ospecs = type(opt_shape)(P(), jax.tree_util.tree_map_with_path(
            lambda path, s: s if optim.indicator_only_mask(path, s) else None,
            pspecs, is_leaf=lambda x: isinstance(x, P)))
        istep = importance_mod.make_importance_step(cfg, ctx, opt, axes,
                                                    remat=remat)
        rng_spec = jax.ShapeDtypeStruct((2,), jnp.uint32)
        jitted = jax.jit(
            istep,
            in_shardings=(_named(mesh, pspecs), _named(mesh, ospecs),
                          _named(mesh, bspecs), None),
            out_shardings=(_named(mesh, pspecs), _named(mesh, ospecs), None),
            donate_argnums=(0, 1))
        return jitted, (params_shape, opt_shape, inputs, rng_spec)

    if step_kind == "prefill":
        if cfg.encoder_only:
            def fwd(params, batch):
                logits, _ = lm.apply_train(params, cfg, batch, bits, ctx,
                                           axes, remat=False)
                return logits
            jitted = jax.jit(fwd,
                             in_shardings=(_named(mesh, pspecs),
                                           _named(mesh, bspecs)),
                             out_shardings=None)
            return jitted, (params_shape, inputs)

        def prefill(params, batch):
            return lm.apply_prefill(params, cfg, batch, bits, ctx, axes,
                                    prefill_cap=shape.seq_len)

        state_shape = jax.eval_shape(
            lambda: lm.init_decode_state(cfg, shape.global_batch,
                                         shape.seq_len))
        sspecs = sharding.decode_state_specs(cfg, state_shape, axes)
        jitted = jax.jit(prefill,
                         in_shardings=(_named(mesh, pspecs),
                                       _named(mesh, bspecs)),
                         out_shardings=(None, _named(mesh, sspecs)))
        return jitted, (params_shape, inputs)

    if step_kind == "decode":
        state_shape = jax.eval_shape(
            lambda: lm.init_decode_state(cfg, shape.global_batch,
                                         shape.seq_len))
        sspecs = sharding.decode_state_specs(cfg, state_shape, axes)

        def serve_step(params, state, token, pos):
            return lm.apply_decode(params, cfg, token, pos, state, bits,
                                   ctx, axes)

        tok = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
        pos = jax.ShapeDtypeStruct((), jnp.int32)
        jitted = jax.jit(serve_step,
                         in_shardings=(_named(mesh, pspecs),
                                       _named(mesh, sspecs),
                                       _named(mesh, sharding.batch_specs(
                                           cfg, tok, axes)), None),
                         out_shardings=(None, _named(mesh, sspecs)),
                         donate_argnums=(1,))
        return jitted, (params_shape, state_shape, tok, pos)

    raise ValueError(step_kind)


def run_cell(arch: str, shape_name: str, mesh_name: str, *,
             step_kind: str = "auto", out_dir: str = "experiments/dryrun",
             save_hlo: bool = False, **build_kw):
    cfg = get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skipped", "reason": why}
    if step_kind == "auto":
        step_kind = {"train": "train", "prefill": "prefill",
                     "decode": "decode"}[shape.kind]

    mesh, mesh_label = make_mesh_by_name(mesh_name)
    n_chips = mesh.devices.size
    t0 = time.time()
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_label,
           "n_chips": n_chips, "step_kind": step_kind}
    try:
        with mesh:
            jitted, args = build_cell(cfg, shape, mesh, step_kind=step_kind,
                                      **build_kw)
            lowered = jitted.lower(*args)
            t_lower = time.time()
            compiled = lowered.compile()
            t_compile = time.time()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        txt = compiled.as_text()
        costs = hlo_mod.analyze(txt)
        rep = roofline.report(arch, shape, mesh_label, n_chips, costs, cfg)
        rec.update({
            "status": "ok",
            "lower_s": round(t_lower - t0, 2),
            "compile_s": round(t_compile - t_lower, 2),
            "memory": {
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "peak_estimate_bytes": mem.argument_size_in_bytes
                + mem.output_size_in_bytes + mem.temp_size_in_bytes
                - mem.alias_size_in_bytes,
            },
            "xla_cost_analysis": {"flops": cost.get("flops", 0.0),
                                  "bytes": cost.get("bytes accessed", 0.0)},
            "hlo_analysis": {
                "flops_per_device": costs.flops,
                "dot_flops_per_device": costs.dot_flops,
                "bytes_hbm_per_device": costs.bytes_hbm,
                "wire_bytes_per_device": costs.wire_bytes,
                "n_collectives": costs.n_collectives,
                "by_collective": costs.by_collective,
                "trip_counts": sorted(set(costs.trip_counts)),
            },
            "roofline": {
                "compute_s": rep.compute_s,
                "memory_s": rep.memory_s,
                "collective_s": rep.collective_s,
                "dominant": rep.dominant,
                "model_flops_total": rep.model_flops_total,
                "useful_ratio": rep.useful_ratio,
                "mfu_at_roofline": rep.mfu,
                "step_time_s": rep.step_time_s,
            },
        })
        if save_hlo:
            os.makedirs(os.path.join(out_dir, "hlo"), exist_ok=True)
            with gzip.open(os.path.join(
                    out_dir, "hlo",
                    f"{arch}__{shape_name}__{mesh_label}.txt.gz"), "wt") as f:
                f.write(txt)
    except Exception as e:           # a failing cell is a bug — record it
        rec.update({"status": "error", "error": repr(e),
                    "traceback": traceback.format_exc()[-4000:]})
    os.makedirs(out_dir, exist_ok=True)
    fname = f"{arch}__{shape_name}__{mesh_label}"
    if step_kind == "importance":
        fname += "__importance"
    with open(os.path.join(out_dir, fname + ".json"), "w") as f:
        json.dump(rec, f, indent=2)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--importance-cell", action="store_true",
                    help="lower the joint-importance (n+1 pass) step for the "
                         "paper-representative arch")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--no-zero", action="store_true")
    ap.add_argument("--no-shard-seq", action="store_true")
    ap.add_argument("--baseline", action="store_true",
                    help="paper-faithful baseline paths: xla_scan flash "
                         "(stored attention residuals), global MoE dispatch, "
                         "no wkv chunk remat")
    args = ap.parse_args()

    if args.baseline:
        from repro.models import attention as _attn
        from repro.models import moe as _moe
        from repro.models import recurrent as _rec
        _attn.FLASH_IMPL = "xla_scan"
        _moe.GROUP_LOCAL_DISPATCH = False
        _rec.WKV_REMAT = False

    archs = list(list_archs()) if args.arch == "all" else [args.arch]
    shapes = [s.name for s in SHAPES] if args.shape == "all" else [args.shape]
    meshes = {"single": ["single"], "multi": ["multi"],
              "both": ["single", "multi"]}[args.mesh]

    if args.list:
        for a in archs:
            cfg = get_config(a)
            for s in shapes:
                ok, why = shape_applicable(cfg, SHAPES_BY_NAME[s])
                print(f"{a:24s} {s:12s} {'RUN' if ok else 'SKIP: ' + why}")
        return

    build_kw = dict(remat=not args.no_remat, zero_shard=not args.no_zero,
                    shard_seq=False if args.no_shard_seq else "auto")
    if args.importance_cell:
        rec = run_cell("qwen3-0.6b", "train_4k", meshes[0],
                       step_kind="importance", out_dir=args.out,
                       save_hlo=args.save_hlo, **build_kw)
        print(json.dumps(rec, indent=2)[:2000])
        return

    n_ok = n_skip = n_err = 0
    for mesh_name in meshes:
        for a in archs:
            for s in shapes:
                rec = run_cell(a, s, mesh_name, out_dir=args.out,
                               save_hlo=args.save_hlo, **build_kw)
                status = rec["status"]
                n_ok += status == "ok"
                n_skip += status == "skipped"
                n_err += status == "error"
                extra = ""
                if status == "ok":
                    r = rec["roofline"]
                    extra = (f"dom={r['dominant']:10s} "
                             f"comp={r['compute_s']*1e3:8.2f}ms "
                             f"mem={r['memory_s']*1e3:8.2f}ms "
                             f"coll={r['collective_s']*1e3:8.2f}ms "
                             f"temp={rec['memory']['temp_bytes']/2**30:6.2f}GiB "
                             f"compile={rec['compile_s']:6.1f}s")
                elif status == "error":
                    extra = rec["error"][:160]
                else:
                    extra = rec["reason"]
                print(f"[{status:7s}] {a:24s} {s:12s} {mesh_name:7s} {extra}",
                      flush=True)
    print(f"done: {n_ok} ok, {n_skip} skipped, {n_err} errors")


if __name__ == "__main__":
    main()
