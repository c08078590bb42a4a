"""Attention: GQA with RoPE, optional qk-norm, sliding-window, cross-attn,
and a KV-cache decode path.

Two execution paths, numerically cross-checked in tests:

* ``direct`` — materializes (B, KV, G, Sq, Sk) logits; used for short
  sequences and decode.
* ``flash`` — pure-JAX online-softmax over q/kv blocks (lax.scan), O(block)
  memory. For sliding-window attention the kv range per q-block is a
  *static-length dynamic slice* of width ~window+q_block, so long-context
  FLOPs scale as S*window, not S^2 (this is what makes long_500k lowerable
  for the SWA archs). For full causal attention all kv blocks are computed
  and masked (countable FLOPs; the ~2x triangle waste is recorded in the
  roofline notes as a known gap).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.runtime import kv_cache as qkv
from repro.runtime.kv_cache import QuantKVCache

Array = jax.Array
NEG_INF = -1e30


def _mask_bias(q_pos: Array, k_pos: Array, causal: bool,
               window: Optional[int]) -> Array:
    """(Sq, Sk) additive bias. k_pos < 0 marks empty cache slots."""
    valid = k_pos[None, :] >= 0
    if causal:
        valid &= k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        valid &= q_pos[:, None] - k_pos[None, :] < window
    return jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)


def _gqa_logits(q: Array, k: Array) -> Array:
    """q (B,Sq,KV,G,hd) x k (B,Sk,KV,hd) -> (B,KV,G,Sq,Sk) in f32."""
    return jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                      preferred_element_type=jnp.float32)


def direct_attention(q: Array, k: Array, v: Array, q_pos: Array, k_pos: Array,
                     *, causal: bool, window: Optional[int]) -> Array:
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qr = q.reshape(B, Sq, KV, G, hd) * (hd ** -0.5)
    logits = _gqa_logits(qr, k) + _mask_bias(q_pos, k_pos, causal, window)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(B, Sq, H, hd)


def _flash_qblock(q_blk: Array, k_blk_src: Array, v_blk_src: Array,
                  qpos_blk: Array, kpos_src: Array, *, causal: bool,
                  window: Optional[int], kv_block: int) -> Array:
    """Online softmax for one q block over all kv blocks of its kv slice."""
    B, qb, KV, G, hd = q_blk.shape
    Lkv = k_blk_src.shape[1]
    n_kv = Lkv // kv_block

    def body(carry, i):
        m, l, acc = carry
        s0 = i * kv_block
        k_blk = jax.lax.dynamic_slice_in_dim(k_blk_src, s0, kv_block, axis=1)
        v_blk = jax.lax.dynamic_slice_in_dim(v_blk_src, s0, kv_block, axis=1)
        kpos = jax.lax.dynamic_slice_in_dim(kpos_src, s0, kv_block, axis=0)
        logits = _gqa_logits(q_blk, k_blk)                    # (B,KV,G,qb,kvb)
        logits += _mask_bias(qpos_blk, kpos, causal, window)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        pv = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(v_blk.dtype), v_blk,
                        preferred_element_type=jnp.float32)
        acc_new = acc * alpha[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KV, G, qb), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, qb), jnp.float32)
    a0 = jnp.zeros((B, KV, G, qb, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), jnp.arange(n_kv))
    out = acc / jnp.maximum(l, 1e-30)[..., None]              # (B,KV,G,qb,hd)
    return out


def flash_attention(q: Array, k: Array, v: Array, *, causal: bool,
                    window: Optional[int], q_block: int = 512,
                    kv_block: int = 512) -> Array:
    """Self-attention over equal-length q/k (training & prefill)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    assert S % q_block == 0, (S, q_block)
    qr = (q.reshape(B, S, KV, G, hd) * (hd ** -0.5))
    nqb = S // q_block

    if window is not None and S > window + q_block:
        # static-length kv slice per q block
        Lkv = ((window + q_block + kv_block - 1) // kv_block) * kv_block
        Lkv = min(Lkv, S)
    else:
        Lkv = S
    kv_block = min(kv_block, Lkv)
    assert Lkv % kv_block == 0, (Lkv, kv_block)

    def per_qblock(carry, i):
        qs = i * q_block
        q_blk = jax.lax.dynamic_slice_in_dim(qr, qs, q_block, axis=1)
        qpos = qs + jnp.arange(q_block)
        start = jnp.clip(qs + q_block - Lkv, 0, S - Lkv)
        k_src = jax.lax.dynamic_slice_in_dim(k, start, Lkv, axis=1)
        v_src = jax.lax.dynamic_slice_in_dim(v, start, Lkv, axis=1)
        kpos = start + jnp.arange(Lkv)
        out = _flash_qblock(q_blk, k_src, v_src, qpos, kpos, causal=causal,
                            window=window, kv_block=kv_block)
        return carry, out

    _, outs = jax.lax.scan(per_qblock, (), jnp.arange(nqb))
    # outs: (nqb, B, KV, G, q_block, hd) -> (B, S, H, hd)
    outs = jnp.moveaxis(outs, 0, 3)            # (B,KV,G,nqb,qb,hd)
    B_, KV_, G_ = outs.shape[:3]
    outs = outs.reshape(B_, KV_, G_, S, hd)
    outs = jnp.moveaxis(outs, 3, 1)            # (B,S,KV,G,hd)
    return outs.reshape(B, S, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# custom-VJP flash attention (FA2-style backward: recompute p from lse)
# ---------------------------------------------------------------------------
# The autodiff of the scan-based flash_attention saves every block's
# probability matrix (f32, O(S * window)) as a scan residual — the dominant
# HBM-traffic term of all attention-arch train cells in the baseline
# roofline (EXPERIMENTS.md §Perf). This path stores only (out, lse) and
# rebuilds p blockwise in the backward, the standard FlashAttention-2
# recomputation, expressed in pure JAX (the Pallas analog on real TPUs
# shares the same schedule).

def _flash_fwd_lse(qr, k, v, *, causal, window, q_block, kv_block):
    """Forward with per-row logsumexp. qr pre-scaled (B,S,KV,G,hd).
    Returns (out (B,S,KV,G,hd) f32, lse (B,KV,G,S) f32).

    On a TPU backend this dispatches to the Pallas kernel
    (repro.kernels.flash_attention): probability tiles stay in VMEM instead
    of streaming through HBM — the fix for the dominant memory-roofline
    term of the attention train cells (EXPERIMENTS.md §Perf). The pure-JAX
    scan below is the CPU/dry-run path, the fallback for a sequence that
    does not tile into kv blocks (``runtime.dispatch.resolve_flash_fwd``
    counts which one ran), and the numerical oracle.
    """
    from repro.runtime import dispatch
    if dispatch.resolve_flash_fwd(qr.shape[1], kv_block) == "pallas":
        from repro.kernels import flash_attention as _fa
        return _fa.flash_fwd_pallas(qr, k, v, causal=causal, window=window,
                                    q_block=q_block, kv_block=kv_block)
    B, S, KV, G, hd = qr.shape
    nqb = S // q_block
    Lkv, kvb = _kv_slice_len(S, window, q_block, kv_block)

    def per_qblock(_, i):
        qs = i * q_block
        q_blk = jax.lax.dynamic_slice_in_dim(qr, qs, q_block, axis=1)
        qpos = qs + jnp.arange(q_block)
        if Lkv == S:
            # full-span kv: keep it STATIC — a traced zero-offset slice
            # hides the staticness from SPMD and forces resharding copies
            k_src, v_src = k, v
            kpos = jnp.arange(S)
        else:
            start = jnp.clip(qs + q_block - Lkv, 0, S - Lkv)
            k_src = jax.lax.dynamic_slice_in_dim(k, start, Lkv, axis=1)
            v_src = jax.lax.dynamic_slice_in_dim(v, start, Lkv, axis=1)
            kpos = start + jnp.arange(Lkv)
        m0 = jnp.full((B, KV, G, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, q_block), jnp.float32)
        a0 = jnp.zeros((B, KV, G, q_block, hd), jnp.float32)

        def body(carry, j):
            m, l, acc = carry
            s0 = j * kvb
            k_blk = jax.lax.dynamic_slice_in_dim(k_src, s0, kvb, axis=1)
            v_blk = jax.lax.dynamic_slice_in_dim(v_src, s0, kvb, axis=1)
            kp = jax.lax.dynamic_slice_in_dim(kpos, s0, kvb, axis=0)
            logits = _gqa_logits(q_blk, k_blk) + _mask_bias(qpos, kp, causal,
                                                            window)
            m_new = jnp.maximum(m, logits.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(logits - m_new[..., None])
            l_new = l * alpha + p.sum(axis=-1)
            pv = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(v_blk.dtype),
                            v_blk, preferred_element_type=jnp.float32)
            return (m_new, l_new, acc * alpha[..., None] + pv), None

        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                      jnp.arange(Lkv // kvb))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        return _, (out, lse)

    _, (outs, lses) = jax.lax.scan(per_qblock, None, jnp.arange(nqb))
    # outs (nqb,B,KV,G,qb,hd) -> (B,S,KV,G,hd); lses (nqb,B,KV,G,qb)
    out = jnp.moveaxis(outs, 0, 3).reshape(B, KV, G, S, hd)
    out = out.transpose(0, 3, 1, 2, 4)                 # (B,S,KV,G,hd)
    lse = jnp.moveaxis(lses, 0, 3).reshape(B, KV, G, S)
    return out, lse


def _kv_slice_len(S, window, q_block, kv_block):
    if window is not None and S > window + q_block:
        Lkv = ((window + q_block + kv_block - 1) // kv_block) * kv_block
        Lkv = min(Lkv, S)
    else:
        Lkv = S
    return Lkv, min(kv_block, Lkv)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_cv(qr, k, v, causal, window, q_block, kv_block):
    out, _ = _flash_fwd_lse(qr, k, v, causal=causal, window=window,
                            q_block=q_block, kv_block=kv_block)
    return out


def _flash_cv_fwd(qr, k, v, causal, window, q_block, kv_block):
    out, lse = _flash_fwd_lse(qr, k, v, causal=causal, window=window,
                              q_block=q_block, kv_block=kv_block)
    return out, (qr, k, v, out, lse)


def _flash_cv_bwd(causal, window, q_block, kv_block, res, dout):
    qr, k, v, out, lse = res
    B, S, KV, G, hd = qr.shape
    nqb = S // q_block
    Lkv, kvb = _kv_slice_len(S, window, q_block, kv_block)
    dout = dout.astype(jnp.float32)
    # D_i = rowsum(dout * out)
    Drow = jnp.sum(dout * out.astype(jnp.float32), axis=-1)   # (B,S,KV,G)
    dk = jnp.zeros((B, S, KV, hd), jnp.float32)
    dv = jnp.zeros((B, S, KV, hd), jnp.float32)

    def per_qblock(carry, i):
        dk, dv = carry
        qs = i * q_block
        q_blk = jax.lax.dynamic_slice_in_dim(qr, qs, q_block, axis=1)
        do_blk = jax.lax.dynamic_slice_in_dim(dout, qs, q_block, axis=1)
        D_blk = jax.lax.dynamic_slice_in_dim(Drow, qs, q_block, axis=1)
        lse_blk = jax.lax.dynamic_slice_in_dim(lse, qs, q_block, axis=3)
        qpos = qs + jnp.arange(q_block)
        if Lkv == S:                       # static full span (see fwd note)
            k_src, v_src, kpos, start = k, v, jnp.arange(S), None
        else:
            start = jnp.clip(qs + q_block - Lkv, 0, S - Lkv)
            k_src = jax.lax.dynamic_slice_in_dim(k, start, Lkv, axis=1)
            v_src = jax.lax.dynamic_slice_in_dim(v, start, Lkv, axis=1)
            kpos = start + jnp.arange(Lkv)
        # recompute p for the whole kv slice of this q block
        logits = _gqa_logits(q_blk, k_src) + _mask_bias(qpos, kpos, causal,
                                                        window)
        p = jnp.exp(logits - lse_blk[..., None])              # (B,KV,G,qb,Lkv)
        # dv_slice += p^T dout ; dp = dout v^T ; ds = p (dp - D)
        do_r = do_blk.reshape(B, q_block, KV, G, hd)
        dv_sl = jnp.einsum("bkgqs,bqkgd->bskd", p, do_r)
        dp = jnp.einsum("bqkgd,bskd->bkgqs", do_r, v_src)
        ds = p * (dp - D_blk.transpose(0, 2, 3, 1)[..., None])
        dq_blk = jnp.einsum("bkgqs,bskd->bqkgd", ds,
                            k_src.astype(jnp.float32))
        dk_sl = jnp.einsum("bkgqs,bqkgd->bskd", ds,
                           q_blk.reshape(B, q_block, KV, G, hd
                                         ).astype(jnp.float32))
        # accumulate: plain whole-array add when the slice spans all of S
        # (keeps the accumulators shardable without dynamic-offset DUS)
        if start is None:
            dk = dk + dk_sl
            dv = dv + dv_sl
        else:
            cur_k = jax.lax.dynamic_slice_in_dim(dk, start, Lkv, axis=1)
            cur_v = jax.lax.dynamic_slice_in_dim(dv, start, Lkv, axis=1)
            dk = jax.lax.dynamic_update_slice_in_dim(dk, cur_k + dk_sl,
                                                     start, axis=1)
            dv = jax.lax.dynamic_update_slice_in_dim(dv, cur_v + dv_sl,
                                                     start, axis=1)
        return (dk, dv), dq_blk

    (dk, dv), dq_blocks = jax.lax.scan(per_qblock, (dk, dv), jnp.arange(nqb))
    dq = dq_blocks.reshape(nqb, B, q_block, KV, G, hd)
    dq = jnp.moveaxis(dq, 0, 1).reshape(B, S, KV, G, hd)
    return dq.astype(qr.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash_cv.defvjp(_flash_cv_fwd, _flash_cv_bwd)

FLASH_IMPL = "custom_vjp"        # "custom_vjp" | "xla_scan" (baseline)


def flash_attention_cv(q: Array, k: Array, v: Array, *, causal: bool,
                       window: Optional[int], q_block: int = 512,
                       kv_block: int = 512) -> Array:
    B, S, H, hd = q.shape
    KV = k.shape[2]
    qr = q.reshape(B, S, KV, H // KV, hd) * (hd ** -0.5)
    out = _flash_cv(qr, k, v, causal, window, q_block, kv_block)
    return out.reshape(B, S, H, hd).astype(q.dtype)


def self_attention(q: Array, k: Array, v: Array, *, causal: bool,
                   window: Optional[int], flash_threshold: int = 2048,
                   q_block: int = 512, kv_block: int = 512,
                   impl: Optional[str] = None) -> Array:
    S = q.shape[1]
    if S >= flash_threshold and S % q_block == 0:
        impl = impl or FLASH_IMPL
        fn = flash_attention_cv if impl == "custom_vjp" else flash_attention
        return fn(q, k, v, causal=causal, window=window,
                  q_block=q_block, kv_block=kv_block)
    pos = jnp.arange(S)
    return direct_attention(q, k, v, pos, pos, causal=causal, window=window)


def cross_attention(q: Array, k: Array, v: Array) -> Array:
    """Text queries over (small) image-token KV; no mask."""
    Skv = k.shape[1]
    q_pos = jnp.arange(q.shape[1])
    k_pos = jnp.arange(Skv)
    return direct_attention(q, k, v, q_pos, k_pos, causal=False, window=None)


# ---------------------------------------------------------------------------
# KV cache (decode) — layouts live in runtime.kv_cache behind the KVCache
# protocol (alloc/append/gather/evict/inventory); the names below are the
# attention-level view plus back-compat delegates for the legacy API.
# ---------------------------------------------------------------------------
KVCache = qkv.FpKVCache          # legacy name for the fp ring container
CACHE_TYPES = qkv.CACHE_TYPES


def init_kv_cache(batch: int, capacity: int, kv_heads: int, hd: int,
                  dtype=jnp.bfloat16, per_slot: bool = False,
                  quant: bool = False,
                  layout: Optional[qkv.KVCacheLayout] = None):
    """Allocate a decode cache via :class:`runtime.kv_cache.KVCacheLayout`
    (the one factory all layouts share). ``quant=True`` without an explicit
    ``layout`` keeps the legacy int8-ring meaning."""
    if layout is None:
        layout = qkv.KVCacheLayout(kind="ring",
                                   quant="int8" if quant else "none")
    return layout.alloc(batch, capacity, kv_heads, hd, dtype=dtype,
                        per_slot=per_slot)


def build_prefill_cache(k: Array, v: Array, S: int, cap: int,
                        kv_quant: str = "none"):
    """Store prefill k/v into a fresh decode cache of ``cap`` rows: the last
    ``cap`` rows when the prompt overflows (sliding-window serving), else
    the prompt plus ``-1``-position headroom for generated tokens.

    ``kv_quant``: "none" stores fp rows; "fake" stores quantize-dequantized
    fp rows (the reference graph's view of an int8 slot); "int8" stores the
    codes + per-head write-time scales in a ``QuantKVCache``. "fake" and
    "int8" dequantize to identical values by construction.
    """
    if cap <= S:
        ks, vs = k[:, -cap:], v[:, -cap:]
        pos = jnp.arange(S - cap, S, dtype=jnp.int32)
    else:
        pad = cap - S
        ks = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vs = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        pos = jnp.concatenate([jnp.arange(S, dtype=jnp.int32),
                               jnp.full((pad,), -1, jnp.int32)])
    if kv_quant == "none":
        return KVCache(k=ks, v=vs, pos=pos)
    if kv_quant == "fake":
        return KVCache(k=qkv.fake_quant_kv(ks), v=qkv.fake_quant_kv(vs),
                       pos=pos)
    if kv_quant == "int8":
        kq, ksc = qkv.quantize_rows(ks)
        vq, vsc = qkv.quantize_rows(vs)
        return QuantKVCache(k=kq, v=vq, k_scale=ksc, v_scale=vsc, pos=pos)
    raise ValueError(f"unknown kv_quant mode {kv_quant!r}")


def build_prefill_cache_from_codes(kq: Array, ksc: Array, vq: Array,
                                   vsc: Array, S: int, cap: int):
    """Like ``build_prefill_cache(..., kv_quant="int8")`` but stores codes +
    scales the caller already computed (the prefill attend quantizes once
    and attends the dequantized view; this stores those exact codes rather
    than re-quantizing the dequantized values, whose re-derived scales
    could differ by an ulp)."""
    if cap <= S:
        sl = slice(S - cap, S)
        kqs, vqs = kq[:, sl], vq[:, sl]
        kscs, vscs = ksc[:, sl], vsc[:, sl]
        pos = jnp.arange(S - cap, S, dtype=jnp.int32)
    else:
        pad = cap - S
        pad4 = ((0, 0), (0, pad), (0, 0), (0, 0))
        pad3 = ((0, 0), (0, pad), (0, 0))
        kqs, vqs = jnp.pad(kq, pad4), jnp.pad(vq, pad4)
        kscs, vscs = jnp.pad(ksc, pad3), jnp.pad(vsc, pad3)
        pos = jnp.concatenate([jnp.arange(S, dtype=jnp.int32),
                               jnp.full((pad,), -1, jnp.int32)])
    return QuantKVCache(k=kqs, v=vqs, k_scale=kscs, v_scale=vscs, pos=pos)


def cache_per_slot(cache):
    """Widen a shared-position KV cache to the per-slot layout.

    Handles plain caches (k (B,Sc,KV,hd), pos (Sc,)) and body-stacked ones
    (k (R,B,Sc,KV,hd), pos (R,Sc)), fp and int8 alike. Other leaves pass
    through, so it can be ``jax.tree.map``-ped over a whole decode-state
    tree with ``is_leaf=lambda x: isinstance(x, CACHE_TYPES)``.
    """
    if not isinstance(cache, CACHE_TYPES):
        return cache
    if isinstance(cache, qkv.PagedKVCache):
        return cache                     # page table is per-slot already
    if cache.k.ndim == 4 and cache.pos.ndim == 1:
        pos = jnp.broadcast_to(cache.pos[None, :],
                               (cache.k.shape[0],) + cache.pos.shape)
    elif cache.k.ndim == 5 and cache.pos.ndim == 2:
        R, B = cache.k.shape[:2]
        pos = jnp.broadcast_to(cache.pos[:, None, :],
                               (R, B, cache.pos.shape[-1]))
    else:
        return cache                     # already per-slot
    return cache._replace(pos=pos)


def _attend_rows(q: Array, k: Array, v: Array, pos_arr: Array, pos: Array,
                 window: Optional[int]) -> Array:
    """Per-slot masked softmax over a full (written) cache: row b attends
    under its own causal/window/validity mask. Rows whose cache is empty
    (all pos -1) softmax over a fully-masked row — finite output, discarded
    by the engine for inactive slots."""
    B, _, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qr = q.reshape(B, 1, KV, G, hd) * (hd ** -0.5)
    logits = _gqa_logits(qr, k)                         # (B,KV,G,1,cap)
    valid = (pos_arr >= 0) & (pos_arr <= pos[:, None])
    if window is not None:
        valid &= pos[:, None] - pos_arr < window
    bias = jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)
    logits = logits + bias[:, None, None, None, :]
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgqs,bskd->bqkgd", probs, v).reshape(B, 1, H, hd)


def ring_write(cache, k_new: Array, v_new: Array, pos):
    """Write one decode token row into the cache — now one ``append`` path
    on the :class:`runtime.kv_cache.KVCache` protocol, shared by every
    layout (fp/int8 ring x shared/per-slot positions, and paged), so their
    semantics cannot drift. For an int8 cache the new row quantizes inside
    ``append`` with its own per-head write-time scale. Returns the updated
    cache."""
    return cache.append(k_new, v_new, pos)


def _attend_quant_fused(q: Array, cache: QuantKVCache, pos: Array,
                        window: Optional[int], route: str) -> Array:
    """Fused decode attention on int8 codes (kernels.quant_attention).
    The shared-position layout broadcasts its mask inputs to the per-slot
    shape the kernel takes; codes/scales pass through untouched."""
    from repro.kernels import ops
    pos_arr, q_pos = cache.pos, pos
    if pos_arr.ndim == 1:
        B = q.shape[0]
        pos_arr = jnp.broadcast_to(pos_arr[None], (B,) + pos_arr.shape)
        q_pos = jnp.broadcast_to(q_pos[None], (B,))
    return ops.decode_attn_quant(
        q, cache.k, cache.k_scale, cache.v, cache.v_scale, pos_arr, q_pos,
        window=window, interpret=True if route == "fused-interpret" else None)


def _attend_paged_fused(q: Array, cache, pos: Array,
                        window: Optional[int], route: str) -> Array:
    """Fused decode attention that gathers pages *by index* inside the
    kernel grid: the page table rides in as a scalar-prefetch operand and
    the block index map points each kv step at its physical page — no
    dense (B, cap) gather materializes in HBM."""
    from repro.kernels import ops
    return ops.decode_attn_quant_paged(
        q, cache.k, cache.k_scale, cache.v, cache.v_scale, cache.pos,
        cache.page_table, pos, window=window,
        interpret=True if route == "fused-interpret" else None)


def decode_attention(q: Array, cache, k_new: Array, v_new: Array,
                     pos, *, window: Optional[int]):
    """One-token decode: ``cache.append`` the new row, then attend. RoPE is
    applied before caching, so slot order is irrelevant to the softmax.
    With a per-slot cache (pos (B, Sc)) ``pos`` is a (B,) vector and each
    row masks independently.

    Int8 layouts (``QuantKVCache`` ring, ``PagedKVCache``) store codes +
    per-head scales instead of fp rows; the attend step routes through
    ``runtime.dispatch.resolve_decode_attn`` — the fused Pallas kernel
    reads the codes directly (TPU, or interpret mode when forced; the
    paged layout uses the gather-by-page-index kernel variant), the
    dequant-fp fallback rebuilds exact fp rows first (default off-TPU, and
    the numerics reference the fused route is token-gated against). The
    paged dequant path attends over ``gather()``'s dense per-slot view,
    which reproduces the ring arrays bit-for-bit.
    """
    out_dtype = v_new.dtype
    new = cache.append(k_new, v_new, pos)
    with jax.named_scope("decode_attn"):
        out = _decode_attend(q, new, jnp.asarray(pos, jnp.int32), window,
                             k_new.dtype, out_dtype)
    return out, new


def _decode_attend(q: Array, new, pos32: Array, window: Optional[int],
                   k_dtype, out_dtype) -> Array:
    """The attend step of ``decode_attention`` over the appended cache, on
    the resolved route."""
    if isinstance(new, qkv.PagedKVCache):
        from repro.runtime import dispatch
        route = dispatch.resolve_decode_attn()
        if route != "dequant-fp":
            out = _attend_paged_fused(q, new, pos32, window, route)
            return out.astype(out_dtype)
        dense = new.gather()
        k = qkv.dequantize(dense.k, dense.k_scale, k_dtype)
        v = qkv.dequantize(dense.v, dense.v_scale, out_dtype)
        return _attend_rows(q, k, v, dense.pos, pos32, window)
    if isinstance(new, QuantKVCache):
        from repro.runtime import dispatch
        route = dispatch.resolve_decode_attn()
        if route != "dequant-fp":
            out = _attend_quant_fused(q, new, pos32, window, route)
            return out.astype(out_dtype)
        k = qkv.dequantize(new.k, new.k_scale, k_dtype)
        v = qkv.dequantize(new.v, new.v_scale, out_dtype)
    else:
        k, v = new.k, new.v
    if new.pos.ndim == 2:
        return _attend_rows(q, k, v, new.pos, pos32, window)
    return direct_attention(q, k, v, pos32[None], new.pos, causal=True,
                            window=window)


def verify_attention(q: Array, cache, k_new: Array, v_new: Array,
                     pos: Array, *, window: Optional[int]):
    """Multi-token verify step for self-speculative decoding: append ALL S
    rows per slot at once (``cache.append_batch`` — the chunked-append
    write path batched over slots), then attend each of the S queries
    through the *exact* single-token decode-attention primitive of the
    resolved route (fused / fused-interpret / dequant-fp, ring and paged
    alike).  ``q (B, S, H, hd)``, ``pos (B, S)`` per-slot absolute
    positions (-1 sentinel rows for inactive slots).

    Exactness contract: query ``j`` masks by its own position, so rows
    written for later queries (and rejected-draft garbage) contribute
    exact zeros after the NEG_INF bias — each query's output is bitwise
    the one-token ``decode_attention`` would produce at that position,
    which is what keeps speculative KV/token streams bitwise identical to
    non-speculative decode per route and per layout.  The fused routes go
    through ``kernels.ops.verify_attn_quant[_paged]``, which unrolls the
    S query positions onto the exact one-token kernel program (S = k + 1,
    small and static) so the whole verify remains one launch.
    """
    out_dtype = v_new.dtype
    pos32 = jnp.asarray(pos, jnp.int32)
    new = cache.append_batch(k_new, v_new, pos32)
    with jax.named_scope("decode_attn"):
        out = _verify_attend(q, new, pos32, window, k_new.dtype, out_dtype)
    return out, new


def _verify_attend(q: Array, new, pos32: Array, window: Optional[int],
                   k_dtype, out_dtype) -> Array:
    """The attend step of ``verify_attention``: each of the S queries
    through the single-token primitive of the resolved route."""
    from repro.runtime import dispatch
    S = q.shape[1]
    paged = isinstance(new, qkv.PagedKVCache)
    quant = isinstance(new, QuantKVCache)
    route = dispatch.resolve_decode_attn() if (paged or quant) \
        else "dequant-fp"
    if route != "dequant-fp":
        from repro.kernels import ops
        interp = True if route == "fused-interpret" else None
        if paged:
            out = ops.verify_attn_quant_paged(
                q, new.k, new.k_scale, new.v, new.v_scale, new.pos,
                new.page_table, pos32, window=window, interpret=interp)
        else:
            assert new.pos.ndim == 2, "verify_attention is per-slot only"
            out = ops.verify_attn_quant(
                q, new.k, new.k_scale, new.v, new.v_scale, new.pos, pos32,
                window=window, interpret=interp)
        return out.astype(out_dtype)
    dense = new.gather() if paged else new
    assert dense.pos.ndim == 2, "verify_attention is per-slot only"
    if isinstance(dense, QuantKVCache):
        k = qkv.dequantize(dense.k, dense.k_scale, k_dtype)
        v = qkv.dequantize(dense.v, dense.v_scale, out_dtype)
    else:
        k, v = dense.k, dense.v
    outs = [_attend_rows(q[:, j:j + 1], k, v, dense.pos, pos32[:, j], window)
            for j in range(S)]
    return jnp.concatenate(outs, axis=1)


def append_attention(q: Array, cache, k_new: Array, v_new: Array,
                     q_pos: Array, slot, *, window: Optional[int]):
    """Chunked-prefill append for one paged slot: quantize-and-write the
    chunk's rows into the slot's pages at absolute positions ``q_pos``
    (-1 pads are dropped), then causally attend the chunk's queries over
    the slot's dense gathered view. Row values and mask sets match the
    dense prefill graph exactly (extra unmapped columns carry ``pos = -1``
    and contribute exact zeros), so a prompt prefilled in chunks decodes
    token-identically to one prefilled densely.
    """
    assert isinstance(cache, qkv.PagedKVCache), type(cache)
    out_dtype = v_new.dtype
    new = cache.append_rows(k_new, v_new, q_pos, slot)
    dense = new.gather_slot(slot)
    k = qkv.dequantize(dense.k, dense.k_scale, k_new.dtype)
    v = qkv.dequantize(dense.v, dense.v_scale, out_dtype)
    out = direct_attention(q, k, v, jnp.asarray(q_pos, jnp.int32),
                           dense.pos[0], causal=True, window=window)
    return out, new
