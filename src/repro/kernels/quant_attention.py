"""Fused int8 decode-attention Pallas kernel (TPU target; interpret-validated,
and compiled for a v5e in tests/test_tpu_compile.py).

The serving engine's int8 KV cache stores codes + per-row per-head f32
scales, but until this kernel the decode step dequantized the *whole* ring
buffer to fp in HBM before attending (``models.attention`` dequant path) —
decode-attention HBM traffic stayed bf16/f32-sized and the ``kv_bits=8``
roofline term was storage-only. Here the codes are the kernel operands:

* K codes (int8) load straight from the cache ring buffer into VMEM; the
  logits compute as ``(q . k_codes) * k_scale`` — the K-scale folds into
  the logit columns *after* the dot, so the MXU/VPU contraction runs on the
  raw codes and HBM never holds a dequantized K row.
* V codes likewise: the PV accumulation is ``(p * v_scale) @ v_codes`` —
  the V-scale rides the probability row into the second dot.
* Masking is position-driven, exactly the dequant reference's inventory:
  a slot attends iff ``0 <= slot_pos <= q_pos`` (and, for sliding-window
  archs, ``q_pos - slot_pos < window``). Ring wraparound therefore needs
  no special handling — slots carry absolute positions, order never
  matters — and evicted slots (``pos == -1``) mask out wherever they sit.
* GQA: the grid runs one program per (batch row, kv head); its q block is
  the (G, hd) group sharing that head, so K/V blocks are fetched once per
  group (same layout trick as ``kernels.flash_attention``).

Softmax state (m, l, acc) lives in VMEM scratch across the sequential kv
grid dimension (online softmax), so capacities larger than one kv block
stream block-by-block. Numerics: logits/probs/PV all accumulate in f32;
the result matches the dequant reference to fp-rounding (scale folding
reassociates one multiply), which preserves greedy-argmax tokens — the
contract the serve smoke and ``benchmarks/quant_serve_bench.py`` gate.

A zero KV row quantizes to codes 0 with the ``KV_SCALE_EPS`` floor scale;
its logit here is ``(q . 0) * eps = 0`` *exactly*, bit-identical to the
reference's ``q . (0 * eps) = 0`` — no ``0 * eps^-1`` term ever forms
because the kernel multiplies by the scale, never divides.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_KV_BLOCK = 256


def _qdec_kernel(qp_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref, pos_ref,
                 o_ref, m_ref, l_ref, acc_ref, *, n_kv, kv_heads, window):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                   # (G, hd) f32, pre-scaled
    kc = k_ref[0].astype(jnp.float32)              # (kvb, hd) from int8 codes
    ks = ks_ref[0]                                 # (1, kvb) f32 row scales
    kpos = pos_ref[0]                              # (1, kvb) int32 abs position
    qp = qp_ref[pl.program_id(0) // kv_heads]      # scalar int32 query pos

    # contraction on the CODES; the K-scale folds into the logit columns in
    # VMEM — a zero row (codes 0, eps-floored scale) lands at exactly 0.0
    logits = jax.lax.dot_general(q, kc, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    logits = logits * ks
    valid = (kpos >= 0) & (kpos <= qp)
    if window is not None:
        valid &= qp - kpos < window
    logits = logits + jnp.where(valid, 0.0, NEG_INF)

    _online_softmax_step(logits, vs_ref[0], v_ref[0], m_ref, l_ref, acc_ref)

    @pl.when(j == n_kv - 1)
    def _finalize():
        _write_out(o_ref, l_ref, acc_ref)


def _online_softmax_step(logits, v_scale, v_codes, m_ref, l_ref, acc_ref):
    """Fold one (G, kvb) logit block into the (m, l, acc) VMEM state. The
    state keeps a trailing unit dim ((G, 1)) so every value stays 2-D on
    the TPU's (sublane, lane) tiles."""
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, logits.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new)                    # (G, kvb)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    # V-scale folds into the probability row; the second dot runs on codes
    pv = jax.lax.dot_general(p * v_scale, v_codes.astype(jnp.float32),
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha + pv
    m_ref[...] = m_new


def _write_out(o_ref, l_ref, acc_ref):
    l = jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _softmax_scratch(G: int, hd: int):
    return [pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32)]


def decode_attn_quant(q, k_codes, k_scale, v_codes, v_scale, pos_arr, q_pos,
                      *, window: Optional[int] = None,
                      kv_block: int = DEFAULT_KV_BLOCK,
                      interpret: bool = False):
    """One-token decode attention directly on int8 KV codes.

    q: (B, 1, H, hd) fp queries; k/v_codes: (B, Sc, KV, hd) int8;
    k/v_scale: (B, Sc, KV) f32 per-row per-head write-time scales;
    pos_arr: (B, Sc) int32 absolute slot positions (-1 = empty);
    q_pos: (B,) int32 per-row query positions. The shared-position cache
    layout broadcasts its ``(Sc,)`` pos / scalar q_pos before calling.
    Returns (B, 1, H, hd) f32.

    Rows whose slots are ALL masked softmax uniformly (the engine discards
    inactive-slot output); note the uniform mean then includes kv-block
    padding slots, so such rows are finite but not comparable against the
    unpadded reference — same contract as the engine's.
    """
    B, Sc, KV, hd = k_codes.shape
    H = q.shape[2]
    G = H // KV
    assert H == KV * G and q.shape[1] == 1, (q.shape, k_codes.shape)

    qf = (q.reshape(B, KV, G, hd).astype(jnp.float32) * (hd ** -0.5))
    qf = qf.reshape(B * KV, G, hd)
    kf = k_codes.transpose(0, 2, 1, 3).reshape(B * KV, Sc, hd)
    vf = v_codes.transpose(0, 2, 1, 3).reshape(B * KV, Sc, hd)
    # scales and positions ride as (rows, 1, Sc): a (1, kvb) block over the
    # last two dims is then (full, lane-aligned), which Mosaic tiles
    ks = k_scale.transpose(0, 2, 1).reshape(B * KV, 1, Sc).astype(jnp.float32)
    vs = v_scale.transpose(0, 2, 1).reshape(B * KV, 1, Sc).astype(jnp.float32)
    pos3 = jnp.asarray(pos_arr, jnp.int32).reshape(B, 1, Sc)
    qp = jnp.asarray(q_pos, jnp.int32).reshape(B)

    kvb = min(kv_block, Sc)
    pad = (-Sc) % kvb
    if pad:
        # padded slots carry pos -1: masked exactly like evicted slots
        kf = jnp.pad(kf, ((0, 0), (0, pad), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad), (0, 0)))
        ks = jnp.pad(ks, ((0, 0), (0, 0), (0, pad)))
        vs = jnp.pad(vs, ((0, 0), (0, 0), (0, pad)))
        pos3 = jnp.pad(pos3, ((0, 0), (0, 0), (0, pad)), constant_values=-1)
    n_kv = (Sc + pad) // kvb

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * KV, n_kv),
        in_specs=[
            pl.BlockSpec((1, G, hd), lambda b, j, qp: (b, 0, 0)),
            pl.BlockSpec((1, kvb, hd), lambda b, j, qp: (b, j, 0)),
            pl.BlockSpec((1, 1, kvb), lambda b, j, qp: (b, 0, j)),
            pl.BlockSpec((1, kvb, hd), lambda b, j, qp: (b, j, 0)),
            pl.BlockSpec((1, 1, kvb), lambda b, j, qp: (b, 0, j)),
            pl.BlockSpec((1, 1, kvb), lambda b, j, qp: (b // KV, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, G, hd), lambda b, j, qp: (b, 0, 0)),
        scratch_shapes=_softmax_scratch(G, hd),
    )
    out = pl.pallas_call(
        functools.partial(_qdec_kernel, n_kv=n_kv, kv_heads=KV,
                          window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * KV, G, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="decode_attn_quant",
        interpret=interpret,
    )(qp, qf, kf, ks, vf, vs, pos3)

    return out.reshape(B, KV, G, hd).reshape(B, 1, H, hd)


# ---------------------------------------------------------------------------
# paged variant: gather-by-page-index via scalar-prefetched page table
# ---------------------------------------------------------------------------
def _qdec_paged_kernel(tbl_ref, qp_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref,
                       pos_ref, o_ref, m_ref, l_ref, acc_ref, *, n_blocks,
                       kv_heads, window):
    p = pl.program_id(0)
    j = pl.program_id(1)
    b = p // kv_heads

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                   # (G, hd) f32, pre-scaled
    kc = k_ref[0, 0].astype(jnp.float32)           # (ps, hd) from int8 codes
    ks = ks_ref[0, 0]                              # (1, ps) f32 row scales
    kpos = pos_ref[0]                              # (1, ps) int32 abs position
    qp = qp_ref[b]                                 # scalar int32 query pos

    logits = jax.lax.dot_general(q, kc, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    logits = logits * ks
    # an unmapped table entry (-1) aliased to physical page 0 by the index
    # map's clip — mask the whole block so it contributes exact zeros
    valid = (tbl_ref[b, j] >= 0) & (kpos >= 0) & (kpos <= qp)
    if window is not None:
        valid &= qp - kpos < window
    logits = logits + jnp.where(valid, 0.0, NEG_INF)

    _online_softmax_step(logits, vs_ref[0, 0], v_ref[0, 0], m_ref, l_ref,
                         acc_ref)

    @pl.when(j == n_blocks - 1)
    def _finalize():
        _write_out(o_ref, l_ref, acc_ref)


def decode_attn_quant_paged(q, k_pages, k_scale, v_pages, v_scale, page_pos,
                            page_table, q_pos, *, window: Optional[int] = None,
                            interpret: bool = False):
    """One-token decode attention over the paged int8 KV layout.

    Same online-softmax body as :func:`decode_attn_quant`, but the kv grid
    dimension walks each slot's *page list* instead of a dense ring: the
    page table and query positions ride in as scalar-prefetch operands
    (``pltpu.PrefetchScalarGridSpec``), and the K/V/scale/pos block index
    maps read ``page_table[slot, j]`` to point block ``j`` at its physical
    page — the gather happens in the block fetch, and HBM never holds a
    densely gathered per-slot cache.

    q: (B, 1, H, hd) fp queries; k/v_pages: (n_pages, ps, KV, hd) int8;
    k/v_scale: (n_pages, ps, KV) f32; page_pos: (n_pages, ps) int32
    absolute positions (-1 = empty row); page_table: (B, P) int32 physical
    page per logical block (-1 = unmapped: its block masks out entirely);
    q_pos: (B,) int32. Returns (B, 1, H, hd) f32.
    """
    n_pages, ps, KV, hd = k_pages.shape
    B, P = page_table.shape
    H = q.shape[2]
    G = H // KV
    assert H == KV * G and q.shape[1] == 1, (q.shape, k_pages.shape)

    qf = (q.reshape(B, KV, G, hd).astype(jnp.float32) * (hd ** -0.5))
    qf = qf.reshape(B * KV, G, hd)
    kf = k_pages.transpose(0, 2, 1, 3)             # (n_pages, KV, ps, hd)
    vf = v_pages.transpose(0, 2, 1, 3)
    # scales (n_pages, KV, 1, ps) and positions (n_pages, 1, ps): each
    # block's last two dims are then the full (1, ps) row
    ks = k_scale.transpose(0, 2, 1).reshape(n_pages, KV, 1, ps).astype(
        jnp.float32)
    vs = v_scale.transpose(0, 2, 1).reshape(n_pages, KV, 1, ps).astype(
        jnp.float32)
    tbl = jnp.asarray(page_table, jnp.int32)
    qp = jnp.asarray(q_pos, jnp.int32)
    pos = jnp.asarray(page_pos, jnp.int32).reshape(n_pages, 1, ps)

    def page_of(p, j, tbl_ref):
        # clip unmapped (-1) to physical page 0; the kernel masks the block
        return jnp.maximum(tbl_ref[p // KV, j], 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B * KV, P),
        in_specs=[
            pl.BlockSpec((1, G, hd), lambda p, j, tbl, qp: (p, 0, 0)),
            pl.BlockSpec((1, 1, ps, hd),
                         lambda p, j, tbl, qp: (page_of(p, j, tbl),
                                                p % KV, 0, 0)),
            pl.BlockSpec((1, 1, 1, ps),
                         lambda p, j, tbl, qp: (page_of(p, j, tbl),
                                                p % KV, 0, 0)),
            pl.BlockSpec((1, 1, ps, hd),
                         lambda p, j, tbl, qp: (page_of(p, j, tbl),
                                                p % KV, 0, 0)),
            pl.BlockSpec((1, 1, 1, ps),
                         lambda p, j, tbl, qp: (page_of(p, j, tbl),
                                                p % KV, 0, 0)),
            pl.BlockSpec((1, 1, ps),
                         lambda p, j, tbl, qp: (page_of(p, j, tbl), 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, hd), lambda p, j, tbl, qp: (p, 0, 0)),
        scratch_shapes=_softmax_scratch(G, hd),
    )
    out = pl.pallas_call(
        functools.partial(_qdec_paged_kernel, n_blocks=P, kv_heads=KV,
                          window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * KV, G, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="decode_attn_quant_paged",
        interpret=interpret,
    )(tbl, qp, qf, kf, ks, vf, vs, pos)

    return out.reshape(B, KV, G, hd).reshape(B, 1, H, hd)


# ---------------------------------------------------------------------------
# multi-token verify (self-speculative decoding)
# ---------------------------------------------------------------------------
def verify_attn_quant(q, k_codes, k_scale, v_codes, v_scale, pos_arr, q_pos,
                      *, window: Optional[int] = None,
                      kv_block: int = DEFAULT_KV_BLOCK,
                      interpret: bool = False):
    """S-token verify attention on int8 KV codes (ring layout).

    ``q (B, S, H, hd)``, ``q_pos (B, S)`` — the speculative verify step
    attends the current token plus the k draft proposals in one launch,
    each query masking by its own absolute position.

    Deliberately UNROLLED over the ``S`` query positions, each reusing the
    EXACT one-token :func:`decode_attn_quant` kernel program (same block
    shapes, same grid, same accumulation order). A true multi-query q
    block would be fewer programs, but changing the operand shapes can
    change tiling — and with it the fp accumulation order — which would
    break the bitwise contract that makes speculative decode KV- and
    token-identical to token-at-a-time decode. ``S = k + 1`` is small and
    static, so the unroll stays one jit launch with S kernel calls.
    """
    outs = [
        decode_attn_quant(q[:, j:j + 1], k_codes, k_scale, v_codes, v_scale,
                          pos_arr, q_pos[:, j], window=window,
                          kv_block=kv_block, interpret=interpret)
        for j in range(q.shape[1])
    ]
    return jnp.concatenate(outs, axis=1)


def verify_attn_quant_paged(q, k_pages, k_scale, v_pages, v_scale, page_pos,
                            page_table, q_pos, *,
                            window: Optional[int] = None,
                            interpret: bool = False):
    """S-token verify attention over the paged int8 KV layout: the paged
    counterpart of :func:`verify_attn_quant`, unrolled over the S query
    positions onto the exact :func:`decode_attn_quant_paged` program for
    the same bitwise-identity reason (see there). ``q (B, S, H, hd)``,
    ``q_pos (B, S)``; rejected-draft rows already written to the pages
    mask out per query position exactly like future rows."""
    outs = [
        decode_attn_quant_paged(q[:, j:j + 1], k_pages, k_scale, v_pages,
                                v_scale, page_pos, page_table, q_pos[:, j],
                                window=window, interpret=interpret)
        for j in range(q.shape[1])
    ]
    return jnp.concatenate(outs, axis=1)
