"""Shared harness for the sharded-serving checks.

One place builds the "packed session under a host mesh vs the same
session on a single device" comparison that both the slow-tier test
(``tests/test_multidevice.py``) and the quantized-serving benchmark
(``benchmarks/quant_serve_bench.py``) run in an 8-device subprocess —
so a change to the session/engine construction or the request preset
cannot drift between the two.

On the CPU it MUST run in a process where
``xla_force_host_platform_device_count`` was set before jax initialized
(the callers spawn a subprocess for exactly that reason); the main
pytest/bench process keeps its single device. On a TPU host it runs over
the chips that are present (``chip_smoke.py --chips 4``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs import get_config, smoke_config
from repro.data import SyntheticLM
from repro.dist import sharding
from repro.dist.axes import NO_AXES, MeshAxes
from repro.launch.engine import DecodeEngine
from repro.launch.mesh import make_mesh
from repro.launch.serve import ServeConfig, build_requests, demo_mixed_policy
from repro.models import lm
from repro.models.quant_layers import QuantContext
from repro.runtime import dispatch
from repro.runtime.session import QuantizedSession

DEFAULT_PRESET = dict(arch="limpq-demo", smoke=True, depth=0, slots=4,
                      prompt_len=16, gen=6, n_requests=6, arrive_every=1)


def run_sharded_vs_single(preset: Dict[str, Any] | None = None,
                          mesh_shape: Tuple[int, int] = (2, 4)):
    """Serve one staggered request set twice — single-device (``NO_AXES``)
    and under a ``mesh_shape`` ('data', 'model') host mesh — through the
    packed quantized runtime. Returns ``(ref_tokens, sharded)`` where
    ``sharded`` carries the mesh run's session/engine/axes/tokens for the
    caller's assertions.

    Both runs take the fp routes: a session bound to a multi-device mesh
    cannot run the Pallas kernels (``runtime.dispatch``), and the token
    gate compares like with like. ``preset["smoke"]`` selects the arch's
    smoke config (default) or its full published config, and a nonzero
    ``preset["depth"]`` cuts its layer count (widths stay)."""
    p = dict(DEFAULT_PRESET, **(preset or {}))
    scfg = ServeConfig(arch=p["arch"], requests=p["n_requests"],
                       slots=p["slots"], prompt_len=p["prompt_len"],
                       gen=p["gen"], stagger=True,
                       arrive_every=p["arrive_every"])
    cfg = smoke_config(scfg.arch) if p["smoke"] else get_config(scfg.arch)
    if p["depth"]:
        cfg = dataclasses.replace(cfg, n_layers=p["depth"])
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    ctx = QuantContext.make(cfg.bits, cfg.quant_act_signed,
                            compute_dtype=jnp.float32)
    policy = demo_mixed_policy(cfg)
    data = SyntheticLM(cfg)
    reqs = build_requests(data, scfg.requests, scfg.prompt_len, scfg.gen,
                          stagger=scfg.stagger,
                          arrive_every=scfg.arrive_every)

    def run(axes: MeshAxes):
        sess = QuantizedSession(cfg, params, policy, ctx, axes,
                                mode="packed", kv_quant="int8")
        eng = DecodeEngine(sess.params, cfg, None, ctx, axes,
                           scfg.engine_config(kv_quant="int8"), adapter=sess)
        eng.submit_all(reqs)
        out = eng.run()
        return sess, eng, {r.rid: out[r.rid].tokens for r in reqs}

    with dispatch.force_impl("dequant-fp"), \
            dispatch.force_decode_attn("dequant-fp"):
        _, _, ref_tokens = run(NO_AXES)
        mesh = make_mesh(mesh_shape, ("data", "model"))
        axes = sharding.make_axes_for(cfg, mesh, shard_seq=False)
        sess, eng, tokens = run(axes)
    return ref_tokens, dict(cfg=cfg, session=sess, engine=eng, axes=axes,
                            tokens=tokens)


def sharded_counters(ref_tokens, sharded) -> Dict[str, Any]:
    """The deterministic, regression-gated view of one harness run —
    the ``sharded_*`` keys of ``BENCH_quant_serve.json``."""
    sess, eng, axes = sharded["session"], sharded["engine"], sharded["axes"]
    per_shard = sess.packed_bytes(per_shard=True)
    budget = sess.per_shard_policy_bytes()
    return {
        "sharded_token_identical": sharded["tokens"] == ref_tokens,
        "sharded_decode_steps": eng.stats.decode_steps,
        "sharded_tokens_generated": eng.stats.tokens_generated,
        "sharded_prefill_compiles": eng.stats.prefill_compiles,
        "sharded_per_shard_vs_policy": per_shard / budget,
        "sharded_tp_size": axes.tp_size,
        "sharded_per_shard_bytes": per_shard,
    }
