"""Config-driven LM covering all 10 assigned architectures.

Layer plumbing
--------------
A config expands into a *schedule*: ``prefix`` layers (unrolled, e.g.
deepseek-moe's first dense layer), a repeating ``pattern`` scanned
``repeats`` times with stacked params (keeps the HLO one-body-per-pattern —
essential for compile time at 52 layers), and ``suffix`` layers (unrolled
remainder, e.g. recurrentgemma's trailing rec-rec).

Every searchable projection is a QLayer (repro.core.qspec) whose per-bit
indicator banks live next to the weight. Bit selection arrives as a
``bits`` pytree that mirrors the param tree: scalars for unrolled layers,
(repeats,)-arrays for scanned ones, so one code path serves
  * full-precision baselines          (bits=None)
  * uniform-bit joint-training passes (bits_uniform)
  * the random communication pass     (bits_random)
  * ILP-searched policies             (bits_from_policy)

Modes: ``train`` (full-seq logits), ``prefill`` (logits at last position +
decode state), ``decode`` (one token with state). Encoder-only archs have
no prefill/decode (DESIGN.md §5).
"""
from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShapeSpec
from repro.core.qspec import QLayer
from repro.core.policy import MPQPolicy
from repro.dist.axes import NO_AXES, MeshAxes
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.runtime import kv_cache as qkv
from repro.models import recurrent as rec_mod
from repro.models.common import activation, apply_norm, embed_init, norm_init
from repro.models.quant_layers import (
    QuantContext, embed_lookup_pinned, qdense_init, qeinsum, qeinsum_pinned,
    pinned_init,
)

Array = jax.Array

FRONTEND_DIMS = {"audio_stub": 512, "vision_stub": 1280, "none": 0}
MOE_AUX_COEF = 0.01


# ===========================================================================
# schedule
# ===========================================================================
class Schedule(NamedTuple):
    prefix: Tuple[str, ...]
    pattern: Tuple[str, ...]
    repeats: int
    suffix: Tuple[str, ...]

    @property
    def n_sites(self) -> int:
        return len(self.prefix) + self.repeats * len(self.pattern) + len(self.suffix)


class LayerSite(NamedTuple):
    kind: str          # attn | dense | moe | cross | rwkv | rec
    segment: str       # "prefix.0" | "body.2" | "suffix.1"
    unit: int          # repeat index within body, else 0
    gidx: int          # global execution index


def build_schedule(cfg: ModelConfig) -> Schedule:
    L = cfg.n_layers
    if cfg.family == "moe":
        fd = cfg.moe.first_dense_layers
        return Schedule(("dense",) * fd, ("moe",), L - fd, ())
    if cfg.family == "vlm":
        cae = cfg.cross_attn_every
        pattern = ("attn",) * cae + ("cross",)
        return Schedule((), pattern, L // cae, ("attn",) * (L % cae))
    if cfg.family == "hybrid":
        bp = tuple(cfg.block_pattern)
        return Schedule((), bp, L // len(bp), bp[: L % len(bp)])
    if cfg.family == "ssm":
        return Schedule((), ("rwkv",), L, ())
    return Schedule((), ("attn",), L, ())    # dense / audio / vlm-less


def iter_sites(cfg: ModelConfig) -> List[LayerSite]:
    s = build_schedule(cfg)
    sites, g = [], 0
    for i, kind in enumerate(s.prefix):
        sites.append(LayerSite(kind, f"prefix.{i}", 0, g))
        g += 1
    for u in range(s.repeats):
        for p, kind in enumerate(s.pattern):
            sites.append(LayerSite(kind, f"body.{p}", u, g))
            g += 1
    for i, kind in enumerate(s.suffix):
        sites.append(LayerSite(kind, f"suffix.{i}", 0, g))
        g += 1
    return sites


def _layer_ff(cfg: ModelConfig, kind: str) -> int:
    if kind == "dense" and cfg.moe and cfg.moe.dense_d_ff:
        return cfg.moe.dense_d_ff
    return cfg.d_ff


# ===========================================================================
# per-kind init
# ===========================================================================
def _mlp_init(rng, cfg: ModelConfig, ff: int, *, stacked=()):
    ks = jax.random.split(rng, 3)
    p = {
        "mlp_wi": qdense_init(ks[0], cfg.d_model, ff, cfg.bits, stacked=stacked),
        "mlp_wo": qdense_init(ks[1], ff, cfg.d_model, cfg.bits, stacked=stacked),
    }
    if cfg.mlp_gated:
        p["mlp_wg"] = qdense_init(ks[2], cfg.d_model, ff, cfg.bits, stacked=stacked)
    return p


def _attn_core_init(rng, cfg: ModelConfig, *, stacked=()):
    ks = jax.random.split(rng, 4)
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    p = {
        "wq": qdense_init(ks[0], d, qd, cfg.bits, stacked=stacked),
        "wk": qdense_init(ks[1], d, kvd, cfg.bits, stacked=stacked),
        "wv": qdense_init(ks[2], d, kvd, cfg.bits, stacked=stacked),
        "wo": qdense_init(ks[3], qd, d, cfg.bits, stacked=stacked),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones(stacked + (cfg.hd,), jnp.float32)
        p["k_norm"] = jnp.ones(stacked + (cfg.hd,), jnp.float32)
    return p


def _layer_init(rng, cfg: ModelConfig, kind: str, *, stacked=()):
    ks = jax.random.split(rng, 4)
    d = cfg.d_model
    nrm = lambda: jax.tree.map(
        lambda a: jnp.broadcast_to(a, stacked + a.shape) if stacked else a,
        norm_init(d, cfg.norm_type))
    if kind in ("attn", "dense", "moe", "cross"):
        p = {"norm1": nrm(), "norm2": nrm()}
        p.update(_attn_core_init(ks[0], cfg, stacked=stacked))
        if kind == "moe":
            p["moe"] = moe_mod.moe_init(ks[1], d, cfg.moe, cfg.bits,
                                        cfg.mlp_gated, stacked=stacked)
        else:
            p.update(_mlp_init(ks[1], cfg, _layer_ff(cfg, kind), stacked=stacked))
        if kind == "cross":
            p["gate_attn"] = jnp.zeros(stacked, jnp.float32)
            p["gate_mlp"] = jnp.zeros(stacked, jnp.float32)
        return p
    if kind == "rwkv":
        p = {"norm1": nrm(), "norm2": nrm()}
        p.update(rec_mod.rwkv_init(ks[0], d, cfg.n_heads, cfg.rwkv_head_dim,
                                   cfg.d_ff, cfg.bits, stacked=stacked))
        return p
    if kind == "rec":
        p = {"norm1": nrm(), "norm2": nrm(),
             "rg": rec_mod.rglru_init(ks[0], d, cfg.lru_width, cfg.n_heads,
                                      cfg.conv1d_width, cfg.bits,
                                      stacked=stacked)}
        p.update(_mlp_init(ks[1], cfg, cfg.d_ff, stacked=stacked))
        return p
    raise ValueError(f"unknown layer kind {kind!r}")


def init_params(rng, cfg: ModelConfig) -> Dict[str, Any]:
    sched = build_schedule(cfg)
    ks = iter(jax.random.split(rng, 8 + sched.n_sites))
    params: Dict[str, Any] = {}

    # --- input embedding / frontend ---------------------------------------
    if cfg.frontend == "audio_stub":
        params["embed"] = pinned_init(next(ks), FRONTEND_DIMS["audio_stub"],
                                      cfg.d_model)
    else:
        params["embed"] = {"w": embed_init(next(ks), cfg.vocab, cfg.d_model)}
        from repro.core.quantizer import bit_range, init_scale_from_stats
        params["embed"]["s_w8"] = init_scale_from_stats(
            params["embed"]["w"], bit_range(8, True)[1])
    if cfg.family == "vlm":
        params["img_proj"] = pinned_init(next(ks), FRONTEND_DIMS["vision_stub"],
                                         cfg.d_model)

    # --- layers ------------------------------------------------------------
    params["prefix"] = {str(i): _layer_init(next(ks), cfg, kind)
                        for i, kind in enumerate(sched.prefix)}
    params["body"] = {str(p): _layer_init(next(ks), cfg, kind,
                                          stacked=(sched.repeats,))
                      for p, kind in enumerate(sched.pattern)} \
        if sched.repeats else {}
    params["suffix"] = {str(i): _layer_init(next(ks), cfg, kind)
                        for i, kind in enumerate(sched.suffix)}

    # --- output ------------------------------------------------------------
    params["final_norm"] = norm_init(cfg.d_model, cfg.norm_type)
    if not cfg.tie_embeddings:
        params["head"] = pinned_init(next(ks), cfg.d_model, cfg.vocab)
    else:
        params["head"] = {"s_a8": jnp.asarray(0.1 / 8, jnp.float32)}
    return params


def param_count(params) -> int:
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))


# ===========================================================================
# QLayer enumeration (must mirror init_params exactly)
# ===========================================================================
def _kind_qdefs(cfg: ModelConfig, kind: str):
    """[(path, in, out, n_mats, macs_per_token, w_params, qkind)]"""
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    if kind in ("attn", "dense", "moe", "cross"):
        qkind = "cross" if kind == "cross" else "attn"
        defs = [
            (("wq",), d, qd, 1, d * qd, d * qd, qkind),
            (("wk",), d, kvd, 1, d * kvd, d * kvd, qkind),
            (("wv",), d, kvd, 1, d * kvd, d * kvd, qkind),
            (("wo",), qd, d, 1, qd * d, qd * d, qkind),
        ]
        if kind == "moe":
            defs += [(("moe",) + path, i, o, n, macs, w, "moe")
                     for path, i, o, n, macs, w, _k
                     in moe_mod.moe_qlayer_defs(d, cfg.moe, cfg.mlp_gated)]
        else:
            ff = _layer_ff(cfg, kind)
            defs += [
                (("mlp_wi",), d, ff, 1, d * ff, d * ff, "mlp"),
                (("mlp_wo",), ff, d, 1, ff * d, ff * d, "mlp"),
            ]
            if cfg.mlp_gated:
                defs.append((("mlp_wg",), d, ff, 1, d * ff, d * ff, "mlp"))
        return defs
    if kind == "rwkv":
        ff = cfg.d_ff
        return [
            (("wr",), d, d, 1, d * d, d * d, "rwkv"),
            (("wk",), d, d, 1, d * d, d * d, "rwkv"),
            (("wv",), d, d, 1, d * d, d * d, "rwkv"),
            (("wg",), d, d, 1, d * d, d * d, "rwkv"),
            (("wo",), d, d, 1, d * d, d * d, "rwkv"),
            (("cm_wk",), d, ff, 1, d * ff, d * ff, "rwkv"),
            (("cm_wv",), ff, d, 1, ff * d, ff * d, "rwkv"),
            (("cm_wr",), d, d, 1, d * d, d * d, "rwkv"),
        ]
    if kind == "rec":
        W = cfg.lru_width or d
        ff = cfg.d_ff
        defs = [
            (("rg", "wx"), d, W, 1, d * W, d * W, "rec"),
            (("rg", "wgate"), d, W, 1, d * W, d * W, "rec"),
            (("rg", "wo"), W, d, 1, W * d, W * d, "rec"),
            (("mlp_wi",), d, ff, 1, d * ff, d * ff, "mlp"),
            (("mlp_wo",), ff, d, 1, ff * d, ff * d, "mlp"),
        ]
        if cfg.mlp_gated:
            defs.append((("mlp_wg",), d, ff, 1, d * ff, d * ff, "mlp"))
        return defs
    raise ValueError(kind)


def enumerate_qlayers(cfg: ModelConfig) -> List[QLayer]:
    out = []
    for site in iter_sites(cfg):
        for path, i, o, n, macs, w, qk in _kind_qdefs(cfg, site.kind):
            out.append(QLayer(
                name=f"L{site.gidx:03d}.{'.'.join(path)}",
                segment=site.segment, unit=site.unit, path=path,
                in_dim=i, out_dim=o, n_mats=n,
                macs_per_token=float(macs), w_params=int(w), kind=qk))
    return out


# ===========================================================================
# bit-assignment pytrees
# ===========================================================================
def _site_bit_template(cfg: ModelConfig, kind: str) -> List[Tuple[str, ...]]:
    return [path for path, *_ in _kind_qdefs(cfg, kind)]


def _nest(dst: dict, path: Tuple[str, ...], leaf):
    for k in path[:-1]:
        dst = dst.setdefault(k, {})
    dst[path[-1]] = leaf


def bits_uniform(cfg: ModelConfig, k) -> Dict[str, Any]:
    """Same bank index `k` (python int or traced scalar) for every QLayer."""
    sched = build_schedule(cfg)
    k = jnp.asarray(k, jnp.int32)
    bits: Dict[str, Any] = {"prefix": {}, "body": {}, "suffix": {}}
    for i, kind in enumerate(sched.prefix):
        d: dict = {}
        for path in _site_bit_template(cfg, kind):
            _nest(d, path, {"w": k, "a": k})
        bits["prefix"][str(i)] = d
    for p, kind in enumerate(sched.pattern):
        if not sched.repeats:
            break
        d = {}
        arr = jnp.broadcast_to(k, (sched.repeats,))
        for path in _site_bit_template(cfg, kind):
            _nest(d, path, {"w": arr, "a": arr})
        bits["body"][str(p)] = d
    for i, kind in enumerate(sched.suffix):
        d = {}
        for path in _site_bit_template(cfg, kind):
            _nest(d, path, {"w": k, "a": k})
        bits["suffix"][str(i)] = d
    return bits


def bits_random(cfg: ModelConfig, rng) -> Dict[str, Any]:
    """Independent random bank index per (QLayer, w/a) — the paper's
    communication pass (§3.4)."""
    sched = build_schedule(cfg)
    n = cfg.n_bits
    bits: Dict[str, Any] = {"prefix": {}, "body": {}, "suffix": {}}

    def draw(shape=()):
        nonlocal rng
        rng, k = jax.random.split(rng)
        return jax.random.randint(k, shape, 0, n, jnp.int32)

    for seg, kinds, shape in (
            ("prefix", sched.prefix, ()),
            ("body", sched.pattern if sched.repeats else (), (sched.repeats,)),
            ("suffix", sched.suffix, ())):
        for i, kind in enumerate(kinds):
            d: dict = {}
            for path in _site_bit_template(cfg, kind):
                _nest(d, path, {"w": draw(shape), "a": draw(shape)})
            bits[seg][str(i)] = d
    return bits


def bits_from_policy(cfg: ModelConfig, policy: MPQPolicy,
                     qlayers: Optional[Sequence[QLayer]] = None) -> Dict[str, Any]:
    """Static per-layer bank indices from an ILP-searched MPQPolicy."""
    qlayers = qlayers if qlayers is not None else enumerate_qlayers(cfg)
    policy.validate(qlayers, bits=cfg.bits)   # stale files fail loudly
    lut = {int(b): i for i, b in enumerate(cfg.bits)}
    per_seg: Dict[str, Dict[Tuple[str, ...], List[Tuple[int, int, int]]]] = {}
    for q in qlayers:
        per_seg.setdefault(q.segment, {}).setdefault(q.path, []).append(
            (q.unit, lut[policy.w_bits[q.name]], lut[policy.a_bits[q.name]]))

    bits: Dict[str, Any] = {"prefix": {}, "body": {}, "suffix": {}}
    for segment, paths in per_seg.items():
        seg, idx = segment.split(".")
        d = bits[seg].setdefault(idx, {})
        for path, triples in paths.items():
            triples.sort()
            w = np.asarray([t[1] for t in triples], np.int32)
            a = np.asarray([t[2] for t in triples], np.int32)
            if seg in ("prefix", "suffix"):
                _nest(d, path, {"w": jnp.asarray(w[0]), "a": jnp.asarray(a[0])})
            else:
                _nest(d, path, {"w": jnp.asarray(w), "a": jnp.asarray(a)})
    return bits


# ===========================================================================
# forward
# ===========================================================================
def _sinusoid_pos(S: int, d: int, dtype) -> Array:
    pos = jnp.arange(S, dtype=jnp.float32)[:, None]
    i = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, 2 * i / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1).astype(dtype)[None]


def embed_inputs(params, cfg: ModelConfig, inputs: Dict[str, Array],
                 ctx: QuantContext, axes: MeshAxes) -> Tuple[Array, Optional[Array]]:
    """Returns (x (B,S,D), img_x (B,N,D) or None)."""
    if cfg.frontend == "audio_stub":
        x = qeinsum_pinned("bsf,fd->bsd", inputs["feats"].astype(ctx.compute_dtype),
                           params["embed"], ctx)
        x = x + _sinusoid_pos(x.shape[1], cfg.d_model, x.dtype)
    else:
        x = embed_lookup_pinned(inputs["tokens"], params["embed"], ctx)
        if cfg.family == "hybrid":          # gemma-style embed scaling
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    img_x = None
    if cfg.family == "vlm" and "img" in inputs:
        img_x = qeinsum_pinned("bnf,fd->bnd",
                               inputs["img"].astype(ctx.compute_dtype),
                               params["img_proj"], ctx)
    x = axes.shard(x, "dp", "sp", None)
    return x, img_x


def _rope_cos_sin(cfg: ModelConfig, positions: Array):
    hd = cfg.hd
    inv = 1.0 / (cfg.rope_theta ** (
        jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    freqs = positions.astype(jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(freqs), jnp.sin(freqs)


def _qk_rms(x: Array, scale: Array, eps: float) -> Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(x.dtype)


def _bget(bits, *path):
    if bits is None:
        return None
    for k in path:
        bits = bits[k]
    return bits


def _proj(eqn: str, x: Array, p, bits, key: str, ctx) -> Array:
    """The projection ``key`` of a layer, under a named scope of its policy
    key (``wq`` ... ``mlp_wo``), so each of its device ops names its site."""
    with jax.named_scope(key):
        return qeinsum(eqn, x, p[key], _bget(bits, key), ctx)


def _attn_window(cfg: ModelConfig, kind: str) -> Optional[int]:
    if cfg.family == "hybrid":
        return cfg.local_window or None
    return cfg.sliding_window


def _attn_sublayer(x, p, bits, cfg: ModelConfig, ctx, axes: MeshAxes, kind: str,
                   mode: str, state, pos, img_x, prefill_cap=None, slot=None):
    """Self- or cross-attention residual sub-block. Returns (x, new_state).

    Modes: ``train`` (no state), ``prefill`` (build a fresh decode cache),
    ``decode`` (one token per batch row), ``append`` (chunked prefill: a
    multi-token chunk for ONE paged slot — ``pos`` is the chunk's absolute
    position vector, ``slot`` the engine slot index), ``verify``
    (speculative multi-token verify: S tokens per slot at per-slot
    absolute positions ``pos (B, S)``, attended per query through the
    single-token decode route — ``attention.verify_attention``)."""
    B, S, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    is_cross = kind == "cross"
    h = apply_norm(x, p["norm1"], cfg.norm_type, cfg.norm_eps)
    h = axes.shard(h, "dp", "sp", None)

    q = _proj("bsd,de->bse", h, p, bits, "wq", ctx)
    q = q.reshape(B, S, H, hd)

    if is_cross:
        if mode == "decode":
            k, v = state                               # cached image k/v
            new_state = state
        else:
            hk = img_x
            k = _proj("bnd,de->bne", hk, p, bits, "wk", ctx)
            v = _proj("bnd,de->bne", hk, p, bits, "wv", ctx)
            k = k.reshape(B, -1, KV, hd)
            v = v.reshape(B, -1, KV, hd)
            k = axes.shard(k, "dp", None, "th", None)
            v = axes.shard(v, "dp", None, "th", None)
            if cfg.qk_norm:
                k = _qk_rms(k, p["k_norm"], cfg.norm_eps)
            new_state = (k, v) if mode == "prefill" else None
        if cfg.qk_norm:
            q = _qk_rms(q, p["q_norm"], cfg.norm_eps)
        out = attn.cross_attention(q, k, v)
    else:
        k = _proj("bsd,de->bse", h, p, bits, "wk", ctx)
        v = _proj("bsd,de->bse", h, p, bits, "wv", ctx)
        k = k.reshape(B, S, KV, hd)
        v = v.reshape(B, S, KV, hd).astype(ctx.compute_dtype)
        # pin the post-reshape layout to a per-dim spec: the projection
        # output arrives sharded on the merged KV*hd dim, and when KV
        # doesn't divide the axis the reshape leaves a multi-dim tiling
        # that downstream slice/concat (rope) must not consume — shard by
        # KV head when it divides, else replicate (megatron keeps KV heads
        # whole per shard)
        q = axes.shard(q, "dp", None, "th", None)
        k = axes.shard(k, "dp", None, "th", None)
        v = axes.shard(v, "dp", None, "th", None)
        if cfg.qk_norm:
            q = _qk_rms(q, p["q_norm"], cfg.norm_eps)
            k = _qk_rms(k, p["k_norm"], cfg.norm_eps)
        if cfg.family != "audio":                      # audio: sinusoid, no rope
            per_slot = mode == "decode" and jnp.ndim(pos) == 1
            if mode == "decode":
                p_ = jnp.asarray(pos, jnp.int32)
                positions = jnp.maximum(p_, 0) if per_slot else p_[None]
            elif mode == "verify":
                # (B, S) per-slot absolute positions (speculative verify);
                # sentinel rows (-1) take angle 0 — masked everywhere
                positions = jnp.maximum(jnp.asarray(pos, jnp.int32),
                                        0).reshape(-1)
            elif mode == "append":
                # chunk of S absolute positions (pad rows carry -1; their
                # rope angle is irrelevant — the cache write drops them)
                positions = jnp.maximum(jnp.asarray(pos, jnp.int32), 0)
            else:
                positions = jnp.arange(S)
            cos, sin = _rope_cos_sin(cfg, positions)
            if per_slot:            # (B, hd/2) -> (B, 1, 1, hd/2): one angle
                cos = cos[:, None, None]    # per slot, broadcast over S and H
                sin = sin[:, None, None]
            elif mode == "verify":  # (B*S, hd/2) -> one angle per (slot,
                cos = cos.reshape(B, S, 1, -1)          # token), broadcast
                sin = sin.reshape(B, S, 1, -1)          # over heads
            from repro.models.common import apply_rope
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
        q = axes.shard(q, "dp", None, "th", None)
        k = k.astype(ctx.compute_dtype)
        window = _attn_window(cfg, kind)
        if mode == "decode":
            if ctx.kv_quant == "fake":
                # reference view of an int8 slot: the new row is stored
                # (and attended) quantize-dequantized, in an fp cache
                k = qkv.fake_quant_kv(k)
                v = qkv.fake_quant_kv(v)
            out, new_state = attn.decode_attention(q, state, k, v, pos,
                                                   window=window)
        elif mode == "verify":
            if ctx.kv_quant == "fake":
                k = qkv.fake_quant_kv(k)
                v = qkv.fake_quant_kv(v)
            out, new_state = attn.verify_attention(
                q, state, k, v, jnp.asarray(pos, jnp.int32), window=window)
        elif mode == "append":
            out, new_state = attn.append_attention(
                q, state, k, v, jnp.asarray(pos, jnp.int32), slot,
                window=window)
        else:
            kq = ksc = vq = vsc = None
            if ctx.kv_quant != "none":
                # quantize ONCE and attend over the dequantized view: the
                # prefill attend then sees exactly the rows a later reader
                # of the cache (decode, or a paged shared-prefix re-prefill
                # that only has the codes) reconstructs. Re-quantizing the
                # dequantized values would round-trip the codes but may
                # perturb the scales by an ulp, so the codes+scales
                # computed here are the ones stored.
                kq, ksc = qkv.quantize_rows(k)
                vq, vsc = qkv.quantize_rows(v)
                k = qkv.dequantize(kq, ksc, k.dtype)
                v = qkv.dequantize(vq, vsc, v.dtype)
            out = attn.self_attention(q.astype(ctx.compute_dtype), k, v,
                                      causal=cfg.causal, window=window)
            if mode == "prefill":
                cap_total = prefill_cap or S
                cap = min(cap_total, window) if window else cap_total
                if ctx.kv_quant == "int8":
                    new_state = attn.build_prefill_cache_from_codes(
                        kq, ksc, vq, vsc, S, cap)
                else:
                    # "fake": k/v already hold the quantize-dequantized
                    # values, so an fp cache of them IS the reference view
                    new_state = attn.build_prefill_cache(k, v, S, cap,
                                                         kv_quant="none")
            else:
                new_state = None
        out = axes.shard(out, "dp", None, "th", None)

    out = out.reshape(B, S, H * hd)
    out = _proj("bse,ed->bsd", out, p, bits, "wo", ctx)
    if is_cross:
        out = out * jnp.tanh(p["gate_attn"]).astype(out.dtype)
    return x + out, new_state


def _mlp_sublayer(x, p, bits, cfg: ModelConfig, ctx, axes: MeshAxes,
                  gate_key: Optional[str] = None):
    h = apply_norm(x, p["norm2"], cfg.norm_type, cfg.norm_eps)
    h = axes.shard(h, "dp", "sp", None)
    hi = _proj("bsd,df->bsf", h, p, bits, "mlp_wi", ctx)
    if cfg.mlp_gated:
        hg = _proj("bsd,df->bsf", h, p, bits, "mlp_wg", ctx)
        hi = activation(cfg.act)(hg) * hi
    else:
        hi = activation(cfg.act)(hi)
    hi = axes.shard(hi, "dp", None, "tp")
    out = _proj("bsf,fd->bsd", hi, p, bits, "mlp_wo", ctx)
    if gate_key is not None:
        out = out * jnp.tanh(p[gate_key]).astype(out.dtype)
    return x + out


def apply_layer(kind: str, x: Array, p, bits, cfg: ModelConfig,
                ctx: QuantContext, axes: MeshAxes, *, mode: str = "train",
                state=None, pos=None, img_x=None, prefill_cap=None,
                slot=None):
    """One residual layer. Returns (x, new_state, aux_loss)."""
    zero = jnp.zeros((), jnp.float32)
    if kind in ("attn", "dense", "cross"):
        st = state
        x, new_st = _attn_sublayer(x, p, bits, cfg, ctx, axes, kind, mode,
                                   st, pos, img_x, prefill_cap, slot)
        x = _mlp_sublayer(x, p, bits, cfg, ctx, axes,
                          gate_key="gate_mlp" if kind == "cross" else None)
        return x, new_st, zero
    if kind == "moe":
        x, new_st = _attn_sublayer(x, p, bits, cfg, ctx, axes, kind, mode,
                                   state, pos, img_x, prefill_cap, slot)
        h = apply_norm(x, p["norm2"], cfg.norm_type, cfg.norm_eps)
        # serving routes dropless; the capacity dispatch is training's
        out, aux = moe_mod.moe_ffn(h, p["moe"], cfg.moe, _bget(bits, "moe"),
                                   ctx, cfg.act, cfg.mlp_gated, axes,
                                   dropless=mode != "train")
        return x + out, new_st, aux
    if kind == "rwkv":
        st = state or (None, None, None)
        h = apply_norm(x, p["norm1"], cfg.norm_type, cfg.norm_eps)
        tm_state = None if st[0] is None else (st[0], st[1])
        out, (xp_tm, wkv) = rec_mod.rwkv_time_mix(
            h, p, bits, ctx, cfg.n_heads, cfg.rwkv_head_dim, state=tm_state)
        x = x + out
        h2 = apply_norm(x, p["norm2"], cfg.norm_type, cfg.norm_eps)
        out2, xp_cm = rec_mod.rwkv_channel_mix(h2, p, bits, ctx, state=st[2])
        new_st = ((xp_tm, wkv, xp_cm) if mode != "train" else None)
        return x + out2, new_st, zero
    if kind == "rec":
        h = apply_norm(x, p["norm1"], cfg.norm_type, cfg.norm_eps)
        out, rg_state = rec_mod.rglru_block(h, p["rg"], _bget(bits, "rg"),
                                            ctx, cfg.n_heads, state=state)
        x = x + out
        x = _mlp_sublayer(x, p, bits, cfg, ctx, axes)
        return x, rg_state if mode != "train" else None, zero
    raise ValueError(kind)


def _seg_bits(bits, seg: str, idx: str):
    if bits is None:
        return None
    return bits[seg][idx]


def run_layers(x: Array, params, bits, cfg: ModelConfig, ctx: QuantContext,
               axes: MeshAxes, *, mode: str = "train", states=None, pos=None,
               img_x=None, remat: bool = True, prefill_cap=None):
    """Run the full layer stack. Returns (x, new_states, aux)."""
    sched = build_schedule(cfg)
    aux = jnp.zeros((), jnp.float32)
    new_states = {"prefix": {}, "body": {}, "suffix": {}} \
        if mode != "train" else None

    def site_state(seg, idx):
        if states is None:
            return None
        return states[seg].get(idx)

    for i, kind in enumerate(sched.prefix):
        x, st, a = apply_layer(kind, x, params["prefix"][str(i)],
                               _seg_bits(bits, "prefix", str(i)), cfg, ctx,
                               axes, mode=mode, state=site_state("prefix", str(i)),
                               pos=pos, img_x=img_x, prefill_cap=prefill_cap)
        aux += a
        if new_states is not None:
            new_states["prefix"][str(i)] = st

    if sched.repeats:
        body_bits = None if bits is None else bits["body"]
        body_states = None if states is None else states["body"]

        def step(carry, xs):
            x, aux = carry
            pp, bb, ss = xs
            sts = {}
            for p_i, kind in enumerate(sched.pattern):
                x, st, a = apply_layer(
                    kind, x, pp[str(p_i)],
                    None if bb is None else bb[str(p_i)], cfg, ctx, axes,
                    mode=mode, state=None if ss is None else ss[str(p_i)],
                    pos=pos, img_x=img_x, prefill_cap=prefill_cap)
                aux += a
                if mode != "train":
                    sts[str(p_i)] = st
            x = axes.shard(x, "dp", "sp", None)
            return (x, aux), (sts if mode != "train" else 0)

        f = jax.checkpoint(step, prevent_cse=False) \
            if (remat and mode == "train") else step
        (x, aux), body_out = jax.lax.scan(
            f, (x, aux), (params["body"], body_bits, body_states))
        if new_states is not None:
            new_states["body"] = body_out

    for i, kind in enumerate(sched.suffix):
        x, st, a = apply_layer(kind, x, params["suffix"][str(i)],
                               _seg_bits(bits, "suffix", str(i)), cfg, ctx,
                               axes, mode=mode, state=site_state("suffix", str(i)),
                               pos=pos, img_x=img_x, prefill_cap=prefill_cap)
        aux += a
        if new_states is not None:
            new_states["suffix"][str(i)] = st

    return x, new_states, aux


@jax.named_scope("lm_head")
def lm_head(x: Array, params, cfg: ModelConfig, ctx: QuantContext,
            axes: MeshAxes) -> Array:
    x = apply_norm(x, params["final_norm"], cfg.norm_type, cfg.norm_eps)
    if cfg.tie_embeddings:
        w = params["embed"]["w"]
        from repro.core.quantizer import bit_range, fake_quant, lsq_grad_scale_factor
        if ctx.enabled:
            qmin, qmax = bit_range(8, True)
            g = lsq_grad_scale_factor(w.size, qmax)
            # the tied head fake-quantizes the whole table in every launch
            with jax.named_scope("head_fake_quant"):
                w = fake_quant(w.astype(jnp.float32), params["embed"]["s_w8"],
                               qmin, qmax, grad_scale_factor=g)
        logits = jnp.einsum("bsd,vd->bsv", x.astype(ctx.compute_dtype),
                            w.astype(ctx.compute_dtype))
    else:
        logits = qeinsum_pinned("bsd,dv->bsv", x, params["head"], ctx)
    return axes.shard(logits.astype(jnp.float32), "dp", None, "tv")


# ===========================================================================
# top-level passes
# ===========================================================================
def apply_train(params, cfg: ModelConfig, inputs, bits, ctx: QuantContext,
                axes: MeshAxes = NO_AXES, remat: bool = True):
    """Full-sequence logits. Returns (logits (B,S,V) f32, aux)."""
    x, img_x = embed_inputs(params, cfg, inputs, ctx, axes)
    x, _, aux = run_layers(x, params, bits, cfg, ctx, axes, mode="train",
                           img_x=img_x, remat=remat)
    return lm_head(x, params, cfg, ctx, axes), aux


def loss_fn(params, cfg: ModelConfig, inputs, bits, ctx: QuantContext,
            axes: MeshAxes = NO_AXES, remat: bool = True):
    """Task loss (CE) + MoE aux. Returns (loss, metrics dict)."""
    logits, aux = apply_train(params, cfg, inputs, bits, ctx, axes, remat=remat)
    if cfg.encoder_only:
        labels = inputs["labels"]
        lg, tg = logits, labels
    else:
        lg, tg = logits[:, :-1], inputs["tokens"][:, 1:]
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, tg[..., None], axis=-1)[..., 0]
    ce = jnp.mean(lse - picked)
    loss = ce + MOE_AUX_COEF * aux
    return loss, {"ce": ce, "moe_aux": aux, "loss": loss}


def trim_decode_state(states, true_len):
    """Invalidate KV rows at positions >= ``true_len`` (fp and int8 caches
    alike). Used by bucketed prefill: a prompt padded at the end to a
    power-of-two length leaves pad-token rows in the cache whose positions
    would otherwise look valid to future decode steps. Non-cache state
    (recurrent, cross-attn image KV) passes through — bucketed prefill is
    gated to attention-only schedules upstream."""
    tl = jnp.asarray(true_len, jnp.int32)

    def one(c):
        if isinstance(c, attn.CACHE_TYPES):
            return c._replace(pos=jnp.where(c.pos < tl, c.pos, -1))
        return c

    return jax.tree.map(one, states,
                        is_leaf=lambda x: isinstance(x, attn.CACHE_TYPES))


def rollback_decode_state(states, cut):
    """Invalidate KV rows at positions >= per-slot ``cut`` ((B,) int32) in
    every cache of a per-slot decode state. This is the speculative-decode
    rollback: draft-written rows past the first rejection are rewound (ring:
    pos sentinel; paged: pos sentinel via the page table) so the cache is
    bitwise identical — pos exactly, codes/scales on all valid rows — to a
    non-speculative engine that decoded only the accepted tokens.
    Non-cache state (recurrent, cross-attn image KV) has no positional
    rows to rewind; speculation is gated to attention-only schedules
    upstream (ServeConfig validation)."""
    cut = jnp.asarray(cut, jnp.int32)

    def one(c):
        if isinstance(c, attn.CACHE_TYPES):
            return c.rollback(cut)
        return c

    return jax.tree.map(one, states,
                        is_leaf=lambda x: isinstance(x, attn.CACHE_TYPES))


def finish_prefill(x, states, params, cfg: ModelConfig, ctx: QuantContext,
                   axes: MeshAxes, true_len=None):
    """Shared prefill epilogue (the bucketing contract lives HERE, for both
    the fake-quant graph and the packed runtime session): read logits at
    the true last position and, for a padded (bucketed) prompt, invalidate
    the cache rows holding pad tokens. Returns (logits (B,V), states)."""
    if true_len is None:
        x_last = x[:, -1:]
    else:
        tl = jnp.asarray(true_len, jnp.int32)
        x_last = jax.lax.dynamic_slice_in_dim(x, tl - 1, 1, axis=1)
        states = trim_decode_state(states, tl)
    logits = lm_head(x_last, params, cfg, ctx, axes)
    return logits[:, 0], states


def apply_prefill(params, cfg: ModelConfig, inputs, bits, ctx: QuantContext,
                  axes: MeshAxes = NO_AXES, prefill_cap=None, true_len=None):
    """Prompt pass. Returns (last-position logits (B,V), decode state).
    `prefill_cap` sizes the KV cache (prompt + generation headroom).

    ``true_len`` (traced scalar) marks the real prompt length inside a
    padded (bucketed) input: logits are read at position ``true_len - 1``
    and cache rows holding pad tokens are invalidated, so one compiled
    prefill serves every prompt length in its bucket."""
    x, img_x = embed_inputs(params, cfg, inputs, ctx, axes)
    x, states, _ = run_layers(x, params, bits, cfg, ctx, axes, mode="prefill",
                              img_x=img_x, remat=False, prefill_cap=prefill_cap)
    return finish_prefill(x, states, params, cfg, ctx, axes, true_len)


def apply_decode(params, cfg: ModelConfig, token: Array, pos, states, bits,
                 ctx: QuantContext, axes: MeshAxes = NO_AXES):
    """One decode step. token (B,1) int32.

    ``pos`` is either a scalar int32 (fixed-batch serving: every row sits at
    the same position, KV caches carry shared ``pos (Sc,)``) or a (B,)
    vector (slot-indexed serving: row b is an independent engine slot at its
    own position, caches carry per-slot ``pos (B, Sc)`` — see
    ``init_decode_state(per_slot=True)``). Per-slot rows mask their own
    cache by position/length, so inactive or shorter slots never see another
    row's KV entries. Returns (logits (B,V), new states)."""
    x, _ = embed_inputs(params, cfg, {"tokens": token}, ctx, axes)
    x, new_states, _ = run_layers(x, params, bits, cfg, ctx, axes,
                                  mode="decode", states=states, pos=pos,
                                  remat=False)
    logits = lm_head(x, params, cfg, ctx, axes)
    return logits[:, 0], new_states


def apply_verify(params, cfg: ModelConfig, tokens: Array, pos, states, bits,
                 ctx: QuantContext, axes: MeshAxes = NO_AXES):
    """Speculative multi-token verify: ``tokens (B, S)`` int32 at per-slot
    absolute positions ``pos (B, S)`` (-1 sentinel rows for inactive
    slots).  One launch computes logits at every position and overwrites
    the S cached KV rows per slot with rows computed under THESE params
    (``attention.verify_attention`` batched append) — for the
    self-speculative engine that is what replaces the draft policy's rows
    with the target policy's, so the surviving cache is bitwise the
    non-speculative one.  Returns (logits (B, S, V) f32, new states)."""
    x, _ = embed_inputs(params, cfg, {"tokens": tokens}, ctx, axes)
    x, new_states, _ = run_layers(x, params, bits, cfg, ctx, axes,
                                  mode="verify", states=states, pos=pos,
                                  remat=False)
    return lm_head(x, params, cfg, ctx, axes), new_states


# ===========================================================================
# decode-state + input specs (ShapeDtypeStruct stand-ins for the dry-run)
# ===========================================================================
def init_site_state(cfg: ModelConfig, kind: str, batch: int, capacity: int,
                    dtype=jnp.bfloat16, per_slot: bool = False,
                    kv_quant: str = "none", layout=None):
    """Fresh decode state for ONE layer site of the given kind.

    ``kv_quant="int8"`` (or "fake" — same fp layout, quantized values)
    selects the int8 KV layout for self-attention sites; recurrent /
    cross-attention state is unaffected. ``layout`` (a
    ``runtime.kv_cache.KVCacheLayout``) overrides the kind/quant flags for
    self-attention sites — it's how the paged pool layout is selected."""
    KV, hd, H = cfg.n_kv_heads, cfg.hd, cfg.n_heads
    W = cfg.lru_width or cfg.d_model
    if kind in ("attn", "dense", "moe"):
        window = _attn_window(cfg, kind)
        cap = min(capacity, window) if window else capacity
        return attn.init_kv_cache(batch, cap, KV, hd, dtype,
                                  per_slot=per_slot,
                                  quant=kv_quant == "int8",
                                  layout=layout)
    if kind == "cross":
        n = cfg.n_image_tokens
        return (jnp.zeros((batch, n, KV, hd), dtype),
                jnp.zeros((batch, n, KV, hd), dtype))
    if kind == "rwkv":
        hdr = cfg.rwkv_head_dim
        return (jnp.zeros((batch, 1, cfg.d_model), dtype),
                jnp.zeros((batch, H, hdr, hdr), jnp.float32),
                jnp.zeros((batch, 1, cfg.d_model), dtype))
    if kind == "rec":
        return (jnp.zeros((batch, cfg.conv1d_width - 1, W), dtype),
                jnp.zeros((batch, W), jnp.float32))
    raise ValueError(kind)


def init_decode_state(cfg: ModelConfig, batch: int, capacity: int,
                      dtype=jnp.bfloat16, per_slot: bool = False,
                      kv_quant: str = "none"):
    """Allocate decode state for a context of `capacity` tokens.

    ``per_slot=True`` lays the KV caches out for the continuous-batching
    engine: the batch dim becomes a slot axis and every cache carries its
    own (batch, cap) position row, so sequences at different positions can
    share one decode step (``apply_decode`` with a (B,) pos vector).
    ``kv_quant="int8"`` stores self-attention KV as int8 codes + per-head
    scales (``runtime.kv_cache.QuantKVCache``)."""
    sched = build_schedule(cfg)

    def site_state(kind):
        return init_site_state(cfg, kind, batch, capacity, dtype=dtype,
                               per_slot=per_slot, kv_quant=kv_quant)

    states = {"prefix": {}, "body": {}, "suffix": {}}
    for i, kind in enumerate(sched.prefix):
        states["prefix"][str(i)] = site_state(kind)
    for p, kind in enumerate(sched.pattern):
        if not sched.repeats:
            break
        states["body"][str(p)] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (sched.repeats,) + a.shape),
            site_state(kind))
    for i, kind in enumerate(sched.suffix):
        states["suffix"][str(i)] = site_state(kind)
    return states


def decode_state_per_slot(states):
    """Widen a prefill-produced decode state to the per-slot layout: every
    KV cache's shared position vector is broadcast to one row per batch
    entry. Non-cache leaves (recurrent states, cross-attn image KV) already
    carry the batch dim and pass through unchanged."""
    return jax.tree.map(attn.cache_per_slot, states,
                        is_leaf=lambda x: isinstance(x, attn.CACHE_TYPES))


def input_specs(cfg: ModelConfig, shape: ShapeSpec) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for every model input of this shape cell."""
    B, S = shape.global_batch, shape.seq_len
    sds = jax.ShapeDtypeStruct
    if shape.kind == "decode":
        d = {"tokens": sds((B, 1), jnp.int32)}
    elif cfg.frontend == "audio_stub":
        d = {"feats": sds((B, S, FRONTEND_DIMS["audio_stub"]), jnp.float32),
             "labels": sds((B, S), jnp.int32)}
    else:
        d = {"tokens": sds((B, S), jnp.int32)}
    if cfg.family == "vlm" and shape.kind != "decode":
        d["img"] = sds((B, cfg.n_image_tokens, FRONTEND_DIMS["vision_stub"]),
                       jnp.float32)
    return d
