"""Quantized-serving benchmark: the packed runtime vs the fake-quant graph.

Runs a mixed (cyclic over the searched widths) policy through
``repro.runtime.session.QuantizedSession`` — packed weights, int8 KV
slots, bucketed prefill — and the fake-quant reference engine on the same
staggered request set, then writes ``benchmarks/out/BENCH_quant_serve.json``:

* deterministic gated metrics (``check_regression.py --profile quant``):
  token identity with the reference graph, decode steps, tokens, measured
  packed-vs-policy HBM ratio, packed-vs-fp32 compression, bucketed prefill
  compile count;
* per-step FLOP/byte counters from the bit-aware roofline
  (``dist.roofline.decode_step_cost``) for the fp16/bf16-KV baseline vs
  the packed+int8-KV runtime — the arithmetic-intensity shift quantized
  serving buys — including the "int8 stored but fp-attended" column
  (``kv_attend="dequant"``) the fused decode-attention kernel removes;
* the routed decode-attention story (gated): the packed engine runs with
  the fused int8 decode-attention kernel forced through the Pallas
  interpreter (``decode_attn_route``), so token identity vs the reference
  graph covers the kernel program, and the measured per-step cache
  traffic (``decode_attn_hbm_bytes`` = codes + scales + pos, from
  ``runtime.kv_cache.cache_bytes``) must match the roofline's
  ``kv_hbm_bytes`` within 5% (``decode_attn_bytes_match``);
* the self-speculative decoding preset (``_spec_counters``): an int4
  draft repack of the same session drafts k=4 tokens per round for the
  searched target policy — token identity with the single-policy engine,
  the acceptance rate, and a measured decode speedup > 1.0x are gated;
* the elastic precision serving preset (``_elastic_counters``): a 3/4/6
  average-bit policy-variant bank served through the admission-time ILP
  controller on a one-request-per-tick ramp — gated on a downshift swap
  firing, per-variant token identity with each generating variant's
  single-policy reference, pool deferrals going flat after the swap,
  zero weight repacks after engine build, and sub-50 ms re-solves;
* wall-clock throughput for the artifact trail (never gated);
* the SHARDED serving path (``--mesh host8``-equivalent: 2-way dp x 4-way
  tp over 8 forced host devices, run in a subprocess so this process
  keeps 1 device): scheduler counters + token identity vs the
  single-device session + measured per-shard-vs-budget ratio, all gated,
  plus the tp roofline's per-shard HBM and all-reduce wire bytes so the
  bench table shows the tp-scaling story.

Usage: PYTHONPATH=src python -m benchmarks.run --only quant_serve_bench
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp

from benchmarks.common import OUT_DIR
from repro.configs import smoke_config
from repro.data import SyntheticLM
from repro.dist import roofline
from repro.dist.axes import NO_AXES
from repro.launch.engine import DecodeEngine, EngineConfig
from repro.launch.serve import build_requests
from repro.models import lm
from repro.models.quant_layers import QuantContext
from repro.runtime.session import QuantizedSession, summarize

BENCH_PATH = os.path.join(OUT_DIR, "BENCH_quant_serve.json")


def bench_preset(fast: bool = True):
    n_req = 6 if fast else 16
    return dict(arch="limpq-demo", slots=4, prompt_len=16, gen=6,
                n_requests=n_req, arrive_every=1)


def shared_prefix_preset(fast: bool = True):
    """The shared-system-prompt workload the paged KV layout wins on:
    every prompt opens with the same ``prompt_len // 2`` tokens (a full
    page), so the paged engine re-maps those pages instead of
    re-prefilling them."""
    return dict(requests=4 if fast else 8, slots=2, prompt_len=16, gen=4,
                page_size=8)


def _shared_prefix_counters(cfg, params, ctx, policy, fast: bool) -> dict:
    """Serve one shared-prefix request set through {ring, paged} x
    {fused-interpret, dequant-fp} engines over ONE packed session.  Gated:
    greedy tokens bitwise-identical between the layouts on both routes,
    paged saves >0 prefill FLOPs via page-table hits, and chunked-append
    prefill compiles exactly one shape (no prompt-length bucketing)."""
    from repro.launch.serve import ServeConfig
    from repro.runtime import dispatch

    sp = shared_prefix_preset(fast)
    scfg = ServeConfig(arch=cfg.name, requests=sp["requests"],
                       slots=sp["slots"], prompt_len=sp["prompt_len"],
                       gen=sp["gen"], stagger=True, arrive_every=1,
                       kv_layout="paged", page_size=sp["page_size"])
    data = SyntheticLM(cfg)
    reqs = build_requests(data, scfg.requests, scfg.prompt_len, scfg.gen,
                          stagger=scfg.stagger,
                          arrive_every=scfg.arrive_every,
                          share_prefix=scfg.prompt_len // 2)
    sess = QuantizedSession(cfg, params, policy, ctx, mode="packed",
                            kv_quant="int8")
    identical = True
    saved = None
    paged = {}
    for route in ("fused-interpret", "dequant-fp"):
        toks = {}
        for layout in ("ring", "paged"):
            with dispatch.force_decode_attn(route):
                eng = DecodeEngine(
                    sess.params, cfg, None, ctx, NO_AXES,
                    scfg.engine_config(layout=layout), adapter=sess)
                eng.submit_all(reqs)
                out = eng.run()
            toks[layout] = {r.rid: out[r.rid].tokens for r in reqs}
            st = eng.stats.as_dict()
            if layout == "paged":
                eng.pool.check()
                saved = st["prefill_flops_saved"]
                paged.update(prefill_tokens=st["prefill_tokens"],
                             prefill_compiles=st["prefill_compiles"],
                             unique_pages=st["kv_unique_pages"])
            else:
                paged["ring_prefill_tokens"] = st["prefill_tokens"]
        identical &= toks["paged"] == toks["ring"]
    return {
        "shared_prefix_token_identical": bool(identical),
        "prefill_flops_saved": float(saved),
        "shared_prefix_prefill_compiles": paged["prefill_compiles"],
        "shared_prefix_prefill_tokens": paged["prefill_tokens"],
        "shared_prefix_ring_prefill_tokens": paged["ring_prefill_tokens"],
        "shared_prefix_unique_pages": paged["unique_pages"],
    }


def spec_preset(fast: bool = True):
    """Self-speculative decoding preset: int4 draft, k=4 rounds, untraced
    (the single fused draft+verify launch the serving path times).  k=4
    is the first round shape the roofline says beats k single steps on
    the demo model; the int4 draft keeps the acceptance rate high enough
    (~0.4) that the measured speedup clears 1.0x with margin on a noisy
    CI host.  Single-slot on purpose: batch-1 latency-bound decode is
    the regime speculation targets — per-launch dispatch overhead
    amortizes over k+1 tokens per round and the win is stable
    (1.7-2.3x here); at slots=4 the round is compute-bound on the tiny
    demo model and the measured ratio straddles 1.0 with host noise."""
    return dict(requests=2 if fast else 4, slots=1, prompt_len=16, gen=24,
                speculate=4, draft_bits=4)


def _spec_counters(cfg, params, ctx, policy, fast: bool) -> dict:
    """Serve one request set through a speculating engine and a
    non-speculative engine over the same dual-pack session.  Gated:
    greedy tokens identical (the acceptance rule compares argmaxes, so
    identity holds by construction — this gate catches rollback/KV bugs,
    not sampling luck), acceptance rate, and decode speedup > 1.0x."""
    from repro.runtime.session import SpecSession

    sp = spec_preset(fast)
    cache_len = sp["prompt_len"] + sp["gen"] + 8  # k-row verify headroom
    data = SyntheticLM(cfg)
    reqs = build_requests(data, sp["requests"], sp["prompt_len"], sp["gen"],
                          stagger=False)
    sess = SpecSession(cfg, params, policy, ctx,
                       draft_w_bits=sp["draft_bits"], kv_quant="int8")

    picked = {}
    for name, spec_k in (("single", 0), ("spec", sp["speculate"])):
        eng = DecodeEngine(
            sess.params, cfg, None, ctx, NO_AXES,
            EngineConfig(slots=sp["slots"], cache_len=cache_len,
                         kv_quant="int8", speculate=spec_k, trace=False),
            adapter=sess)
        eng.submit_all(reqs)
        eng.run()                                 # warmup: pay the jits
        best = None
        for _ in range(3):                        # best-of-3 measured
            eng.reset()
            eng.submit_all(reqs)
            completions = eng.run()
            st = eng.stats
            if best is None or st.t_decode_s < best[0].t_decode_s:
                best = (st, {r.rid: completions[r.rid].tokens
                             for r in reqs})
        picked[name] = best

    single_st, single_toks = picked["single"]
    spec_st, spec_toks = picked["spec"]
    speedup = (single_st.t_decode_s / spec_st.t_decode_s
               if spec_st.t_decode_s else float("nan"))
    return {
        "spec_token_identical": bool(spec_toks == single_toks),
        "spec_accept_rate": float(spec_st.spec_accept_rate),
        "spec_rounds": spec_st.spec_rounds,
        "spec_draft_tokens": spec_st.spec_draft_tokens,
        "spec_tokens_per_s": spec_st.decode_tokens_per_s,
        "single_policy_tokens_per_s": single_st.decode_tokens_per_s,
        "spec_speedup_vs_single": float(speedup),
        "spec_speedup_gt_1": bool(speedup > 1.0),
    }


def elastic_preset(fast: bool = True):
    """Elastic precision serving: the traffic ramp that forces a swap.
    One request per tick into 2 slots builds a queue fast enough that the
    admission-time ILP re-solve downshifts the active variant; the 3/4/6
    average-bit budgets match the serve --elastic default bank."""
    return dict(requests=8 if fast else 16, slots=2, prompt_len=16, gen=6,
                arrive_every=1, budgets=(3.0, 4.0, 6.0))


def _elastic_counters(cfg, params, ctx, fast: bool) -> dict:
    """Serve the ramp through a variant bank + elastic controller.  Gated:
    at least one downshift swap fires, per-request tokens are bitwise
    identical to the generating variant's single-policy reference, the
    pool-pressure deferral counter stays flat once the swap lands (the
    whole point of degrading precision under load), zero weight repacks
    after engine build, and every admission re-solve closes under 50 ms."""
    from repro.launch import elastic
    from repro.runtime import packing
    from repro.runtime.session import ElasticSession, bank_fingerprint

    ep = elastic_preset(fast)
    cache_len = ep["prompt_len"] + ep["gen"]
    data = SyntheticLM(cfg)
    reqs = build_requests(data, ep["requests"], ep["prompt_len"], ep["gen"],
                          stagger=True, arrive_every=ep["arrive_every"])
    ql = lm.enumerate_qlayers(cfg)
    bank = elastic.build_variant_bank(ql, cfg.bits, ep["budgets"],
                                      family=bank_fingerprint(params))
    sess = ElasticSession(cfg, params, bank.policies, ctx,
                          active=bank.full)
    ctrl = elastic.ElasticController(cfg, bank, slots=ep["slots"],
                                     cache_len=cache_len)
    eng = DecodeEngine(
        sess.params, cfg, None, ctx, NO_AXES,
        EngineConfig(slots=ep["slots"], cache_len=cache_len,
                     kv_quant="int8"),
        adapter=sess, elastic=ctrl)
    # hot-path contract: swaps device_put pre-packed trees, they never
    # repack — count pack_linear calls from here on (build already paid)
    repacks = {"n": 0}
    real_pack = packing.pack_linear

    def counting_pack(*a, **kw):
        repacks["n"] += 1
        return real_pack(*a, **kw)

    # per-iteration (swaps, pool-deferral) series for the flatness gate
    series = []
    eng.on_step = lambda m: series.append(
        (m.value("engine.policy_swaps"),
         m.value("scheduler.admissions_deferred_pool")))
    packing.pack_linear = counting_pack
    try:
        eng.submit_all(reqs)
        completions = eng.run()
    finally:
        packing.pack_linear = real_pack
    st = eng.stats

    # once the controller traded precision for load, pool pressure must
    # stop deferring admissions — the deferral counter goes flat
    after = [d for swaps, d in series if swaps >= 1]
    deferred_flat = (not after) or after[-1] == after[0]

    per_variant = {}
    for c in completions.values():
        per_variant.setdefault(c.policy_id, []).append(c.rid)
    identical = True
    for pid, rids in sorted(per_variant.items()):
        vbits = lm.bits_from_policy(cfg, bank.policies[pid])
        ref = DecodeEngine(
            params, cfg, vbits, ctx, NO_AXES,
            EngineConfig(slots=ep["slots"], cache_len=cache_len,
                         kv_quant="fake"))
        ref.submit_all([r for r in reqs if r.rid in set(rids)])
        ref_out = ref.run()
        identical &= all(ref_out[rid].tokens == completions[rid].tokens
                         for rid in rids)
    return {
        "elastic_swaps": st.policy_swaps,
        "elastic_downshifts": st.policy_swaps_down,
        "elastic_token_identical": bool(identical),
        "elastic_admissions_deferred":
            int(eng.metrics.value("scheduler.admissions_deferred_pool")),
        "elastic_deferred_flat_after_swap": bool(deferred_flat),
        "elastic_repacks_after_build": repacks["n"],
        "elastic_ilp_solves": st.ilp_solves,
        "elastic_ilp_solve_ms_max": float(ctrl.max_solve_ms),
        "elastic_variants_resident": len(sess.variants),
        "elastic_final_variant": st.active_policy,
        "elastic_swap_holds": st.admissions_deferred_swap,
    }


def _mixed_policy(cfg):
    # the same builder the serve --policy smoke uses: the checked-in
    # baseline pins this exact bit assignment
    from repro.launch.serve import demo_mixed_policy
    return lm.enumerate_qlayers(cfg), demo_mixed_policy(cfg)


def _step_counters(cfg, slots, cache_len, *, kv_bits, w_bits_total=None,
                   avg_weight_bits=32.0, tp_size=1, kv_attend="fused"):
    cost = roofline.decode_step_cost(
        cfg, slots, cache_tokens=cache_len, kv_bits=kv_bits,
        w_bits_total=w_bits_total, avg_weight_bits=avg_weight_bits,
        tp_size=tp_size, kv_attend=kv_attend)
    chip = roofline.DEFAULT_CHIP
    flops = cost["compute_s"] * chip.peak_flops
    hbm = cost["memory_s"] * chip.hbm_bytes_s
    return {"step_flops": flops, "step_hbm_bytes": hbm,
            "flops_per_byte": flops / hbm if hbm else 0.0,
            "step_s_model": cost["step_s"], "dominant": cost["dominant"],
            # per-shard HBM + tp all-reduce wire bytes (tp-scaling story)
            "per_shard_hbm_bytes": cost["hbm_bytes"],
            "allreduce_wire_bytes": cost["wire_bytes"]}


# The --mesh host8 serving path, measured in a subprocess: the forced
# 8-device host platform must be set before jax initializes, and this
# process keeps its single device for the main bench. The harness itself
# is shared with tests/test_multidevice.py (repro.runtime.sharded_smoke).
_SHARDED_SCRIPT = r"""
import os, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
from repro.runtime import sharded_smoke

preset = json.loads(os.environ["QS_BENCH_PRESET"])
ref, sharded = sharded_smoke.run_sharded_vs_single(preset)
print("QS_SHARDED " + json.dumps(sharded_smoke.sharded_counters(ref, sharded)))
"""


def _sharded_counters(preset) -> dict:
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    tail = env.get("PYTHONPATH", "")
    env["PYTHONPATH"] = src + (os.pathsep + tail if tail else "")
    env["QS_BENCH_PRESET"] = json.dumps(preset)
    # the child is a count gate on forced host devices; this process may
    # already hold an accelerator, which a second process cannot open
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    for line in out.stdout.splitlines():
        if line.startswith("QS_SHARDED "):
            return json.loads(line[len("QS_SHARDED "):])
    raise RuntimeError(
        f"sharded bench subprocess produced no counters:\n"
        f"{out.stdout[-1000:]}\n{out.stderr[-2000:]}")


def run(fast: bool = True):
    p = bench_preset(fast)
    cfg = smoke_config(p["arch"])
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    ctx = QuantContext.make(cfg.bits, cfg.quant_act_signed,
                            compute_dtype=jnp.float32)
    ql, policy = _mixed_policy(cfg)
    data = SyntheticLM(cfg)
    reqs = build_requests(data, p["n_requests"], p["prompt_len"], p["gen"],
                          stagger=True, arrive_every=p["arrive_every"])
    cache_len = p["prompt_len"] + p["gen"]

    # the packed engine serves with the fused int8 decode-attention kernel
    # on the hot path (interpret mode — the TPU program, executed
    # step-by-step): the token-identity gate below therefore proves the
    # kernel against the dequant reference over a full staggered workload.
    # The force scope wraps build AND runs (route resolves at trace time).
    from repro.runtime import dispatch, kv_cache as qkv

    with dispatch.force_decode_attn("fused-interpret"):
        sess = QuantizedSession(cfg, params, policy, ctx, mode="packed",
                                kv_quant="int8")
        packed_eng = DecodeEngine(
            sess.params, cfg, None, ctx, NO_AXES,
            EngineConfig(slots=p["slots"], cache_len=cache_len,
                         kv_quant="int8", bucket_prompts=True),
            adapter=sess)
        bits = lm.bits_from_policy(cfg, policy, ql)
        ref_eng = DecodeEngine(
            params, cfg, bits, ctx, NO_AXES,
            EngineConfig(slots=p["slots"], cache_len=cache_len,
                         kv_quant="fake"))

        results = {}
        for name, eng in (("packed", packed_eng), ("reference", ref_eng)):
            eng.submit_all(reqs)    # warmup pass: pay the jit compiles
            eng.run()
            eng.reset()
            eng.submit_all(reqs)
            completions = eng.run()
            results[name] = {
                "stats": eng.stats.as_dict(),
                "tokens": {r.rid: completions[r.rid].tokens for r in reqs},
            }

    # measured per-step decode-attention cache traffic: the fused route
    # scans the whole ring buffer every step, so one step's traffic is the
    # resident inventory — codes + scales + pos over every layer cache
    measured_kv = qkv.tree_cache_bytes(packed_eng.state)
    model_kv = roofline.decode_step_cost(
        cfg, p["slots"], cache_tokens=cache_len, kv_bits=8.0,
        kv_attend="fused")["kv_hbm_bytes"]
    kv_ratio = model_kv / measured_kv if measured_kv else float("nan")

    identical = results["packed"]["tokens"] == results["reference"]["tokens"]
    info = summarize(sess)
    w_bits_total = policy.size_bytes(ql) * 8.0
    counters = {
        "fp": _step_counters(cfg, p["slots"], cache_len, kv_bits=16.0,
                             avg_weight_bits=16.0),
        "quantized": _step_counters(cfg, p["slots"], cache_len, kv_bits=8.0,
                                    w_bits_total=w_bits_total),
        # int8 stored but fp-attended: what the dequant fallback pays per
        # step — the honesty gap the fused decode-attention kernel closes
        "quantized_fp_attended": _step_counters(
            cfg, p["slots"], cache_len, kv_bits=8.0,
            w_bits_total=w_bits_total, kv_attend="dequant"),
        # per-shard view of the same quantized step under 4-way tp: HBM
        # per chip and the megatron all-reduce bytes the tp split pays
        "quantized_tp4": _step_counters(cfg, p["slots"], cache_len,
                                        kv_bits=8.0,
                                        w_bits_total=w_bits_total,
                                        tp_size=4),
    }
    sharded = _sharded_counters(p)
    shared_prefix = _shared_prefix_counters(cfg, params, ctx, policy, fast)
    spec = _spec_counters(cfg, params, ctx, policy, fast)
    elastic_m = _elastic_counters(cfg, params, ctx, fast)
    pstats = results["packed"]["stats"]
    # pack-time quantization health: the demo policy packs from its own
    # init's trained-scale bank, so saturation stays near zero and the
    # engine's saturation watcher must never trip (alerts_fired == 0 is
    # gated — a baseline regression here means scales stopped covering
    # the served weights)
    from repro.obs import health as obs_health
    pack_health = obs_health.pack_summary(sess.pack_health)
    # measured-vs-modeled phase ratios from the packed engine's (warmed)
    # measured epoch — the roofline calibration loop, ungated in CI: the
    # ratios are host-dependent, their *presence and finiteness* is not
    from repro.obs import calibrate
    calib = calibrate.calibrate(
        cfg, pstats, slots=p["slots"], cache_tokens=cache_len,
        kv_bits=packed_eng.kv_bits, kv_attend=packed_eng.kv_attend,
        w_bits_total=w_bits_total)
    assert calib["finite"], \
        f"roofline calibration produced non-finite ratios: {calib['rows']}"
    out = {
        "preset": p,
        "token_identical": identical,
        # gated (deterministic)
        "decode_steps": pstats["decode_steps"],
        "tokens_generated": pstats["tokens_generated"],
        "prefill_compiles": pstats["prefill_compiles"],
        "packed_vs_policy": info["packed_vs_policy"],
        "packed_vs_fp32": 1.0 / info["compression_vs_fp32"],
        "decode_attn_route": pstats["decode_attn_route"],
        "decode_attn_hbm_bytes": int(measured_kv),
        "decode_attn_model_vs_measured": kv_ratio,
        "decode_attn_bytes_match": bool(abs(kv_ratio - 1.0) <= 0.05),
        "saturation_rate_max": pack_health["saturation_rate_max"],
        "alerts_fired": pstats["alerts_fired"],
        "scale_utilization_p50": pack_health["scale_utilization_p50"],
        # informational
        "packed_bytes": info["packed_bytes"],
        "scale_bytes": info["scale_bytes"],
        "policy_bytes": info["policy_bytes"],
        "fp32_bytes": info["fp32_bytes"],
        "avg_bits_w": info["avg_bits"][0],
        "avg_bits_a": info["avg_bits"][1],
        "reference_prefill_compiles":
            results["reference"]["stats"]["prefill_compiles"],
        "step_counters": counters,
        "hbm_bytes_saved_per_step":
            counters["fp"]["step_hbm_bytes"]
            - counters["quantized"]["step_hbm_bytes"],
        "packed_tok_per_s": pstats["decode_tokens_per_s"],
        "reference_tok_per_s":
            results["reference"]["stats"]["decode_tokens_per_s"],
        # request-latency percentiles from the engine's metrics registry
        # (wall-clock: artifact trail only, never gated)
        "ttft_p50_ms": pstats.get("ttft_p50_ms", 0.0),
        "ttft_p95_ms": pstats.get("ttft_p95_ms", 0.0),
        "itl_p50_ms": pstats.get("itl_p50_ms", 0.0),
        "itl_p95_ms": pstats.get("itl_p95_ms", 0.0),
        "roofline_modeled_vs_measured": {
            r["phase"]: r["ratio"] for r in calib["rows"]},
    }
    out.update(sharded)
    out.update(shared_prefix)
    out.update(spec)
    out.update(elastic_m)
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(BENCH_PATH, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
    print(f"  token_identical={identical} | packed {info['packed_bytes']} B "
          f"= x{info['packed_vs_policy']:.3f} of policy accounting, "
          f"{info['compression_vs_fp32']:.2f}x under fp32 | decode steps "
          f"{out['decode_steps']} | prefill shapes {out['prefill_compiles']} "
          f"(reference {out['reference_prefill_compiles']})")
    print(f"  roofline step bytes: fp {counters['fp']['step_hbm_bytes']:.2e}"
          f" -> quantized {counters['quantized']['step_hbm_bytes']:.2e} "
          f"(fp-attended int8: "
          f"{counters['quantized_fp_attended']['step_hbm_bytes']:.2e})")
    print(f"  decode-attn route {out['decode_attn_route']} | cache traffic "
          f"{out['decode_attn_hbm_bytes']} B/step measured, model x"
          f"{kv_ratio:.3f}")
    tp4 = counters["quantized_tp4"]
    print(f"  tp=4 per-shard HBM {tp4['per_shard_hbm_bytes']:.2e} B/step | "
          f"all-reduce {tp4['allreduce_wire_bytes']:.2e} B/step | sharded "
          f"serve: tokens_identical={sharded['sharded_token_identical']} "
          f"per-shard x{sharded['sharded_per_shard_vs_policy']:.3f} of "
          f"budget on tp={sharded['sharded_tp_size']}")
    print(f"  shared-prefix preset: tokens_identical="
          f"{shared_prefix['shared_prefix_token_identical']} | paged saved "
          f"{shared_prefix['prefill_flops_saved']:.2e} prefill FLOPs "
          f"({shared_prefix['shared_prefix_prefill_tokens']} prefill tokens "
          f"vs ring {shared_prefix['shared_prefix_ring_prefill_tokens']}) | "
          f"{shared_prefix['shared_prefix_prefill_compiles']} compile "
          f"shape(s)")
    print(f"  self-speculative (k={spec_preset(fast)['speculate']}, int"
          f"{spec_preset(fast)['draft_bits']} draft): tokens_identical="
          f"{spec['spec_token_identical']} | accept rate "
          f"{spec['spec_accept_rate']:.2f} over {spec['spec_rounds']} "
          f"rounds | {spec['spec_tokens_per_s']:.1f} tok/s vs single "
          f"{spec['single_policy_tokens_per_s']:.1f} = x"
          f"{spec['spec_speedup_vs_single']:.2f}")
    print(f"  elastic ramp ({len(elastic_preset(fast)['budgets'])}-variant "
          f"bank): {elastic_m['elastic_swaps']} swap(s), "
          f"{elastic_m['elastic_downshifts']} down | tokens_identical="
          f"{elastic_m['elastic_token_identical']} | "
          f"{elastic_m['elastic_ilp_solves']} re-solves, max "
          f"{elastic_m['elastic_ilp_solve_ms_max']:.1f} ms | held "
          f"{elastic_m['elastic_swap_holds']} round(s) | pool deferrals "
          f"{elastic_m['elastic_admissions_deferred']} (flat after swap: "
          f"{elastic_m['elastic_deferred_flat_after_swap']}) | final "
          f"{elastic_m['elastic_final_variant']}")
    print(f"  pack health: saturation_rate_max="
          f"{pack_health['saturation_rate_max']:.4f} "
          f"scale_utilization_p50="
          f"{pack_health['scale_utilization_p50']:.3f} over "
          f"{pack_health['sites']} sites | alerts_fired="
          f"{out['alerts_fired']}")
    print(f"  -> {BENCH_PATH}")
    assert shared_prefix["shared_prefix_token_identical"], \
        "paged layout diverged from the ring layout on a shared prefix"
    assert shared_prefix["prefill_flops_saved"] > 0, \
        "shared-prefix preset saved no prefill FLOPs (prefix reuse broken)"
    assert shared_prefix["shared_prefix_prefill_compiles"] == 1, \
        "paged chunked-append prefill compiled more than one shape"
    assert identical, "packed runtime diverged from the fake-quant reference"
    assert spec["spec_token_identical"], \
        "speculative decode diverged from the single-policy engine"
    assert spec["spec_speedup_gt_1"], \
        (f"speculative decode did not beat single-policy decode "
         f"(x{spec['spec_speedup_vs_single']:.2f}, accept rate "
         f"{spec['spec_accept_rate']:.2f})")
    assert abs(info["packed_vs_policy"] - 1.0) <= 0.05, \
        "packed HBM bytes off the policy accounting by more than 5%"
    assert sharded["sharded_token_identical"], \
        "sharded session diverged from the single-device session"
    assert sharded["sharded_per_shard_vs_policy"] <= 1.05, \
        "per-shard packed bytes exceed policy.size_bytes/tp beyond padding"
    assert out["decode_attn_route"] == "fused-interpret", \
        "packed engine did not run the fused decode-attention route"
    assert out["decode_attn_bytes_match"], \
        (f"decode_step_cost kv bytes off the measured cache inventory by "
         f"more than 5% (x{kv_ratio:.3f})")
    assert elastic_m["elastic_downshifts"] >= 1, \
        "elastic ramp triggered no downshift swap"
    assert elastic_m["elastic_token_identical"], \
        "elastic completion diverged from its variant's single-policy run"
    assert elastic_m["elastic_deferred_flat_after_swap"], \
        "pool-pressure deferrals kept growing after the downshift swap"
    assert elastic_m["elastic_repacks_after_build"] == 0, \
        "policy hot-swap repacked weights after engine build"
    assert elastic_m["elastic_ilp_solve_ms_max"] < 50.0, \
        (f"admission-time ILP re-solve took "
         f"{elastic_m['elastic_ilp_solve_ms_max']:.1f} ms (>= 50 ms: the "
         "paper's ~0.06 s one-shot search claim is load-bearing here)")
    assert out["alerts_fired"] == 0, \
        (f"{out['alerts_fired']} monitor alert(s) fired on the demo preset "
         f"(saturation_rate_max={out['saturation_rate_max']:.4f}): "
         "the signal plane must stay quiet on a healthy workload")
    return out


if __name__ == "__main__":
    run()
