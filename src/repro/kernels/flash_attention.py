"""Flash-attention forward Pallas kernel (TPU target; interpret-validated, and
compiled for a v5e in tests/test_tpu_compile.py).

The §Perf analysis (EXPERIMENTS.md) shows the optimized attention cells are
bound by per-block probability tiles streaming through HBM — an artifact of
the XLA-only lowering. This kernel is the TPU-native fix: the online-softmax
state (m, l, acc) and the (qb, kvb) probability tile live in VMEM scratch
across the sequential kv grid dimension; HBM sees only q/k/v in and
(out, lse) back.

GQA layout: q rows are (B*KV*G); k/v rows are (B*KV) — the index map folds
the group dim (bh // G) so kv blocks are fetched once per group.

The backward pairs this forward with the recompute-based custom-VJP in
`models/attention.py` (same residuals: out + lse), so training uses the
kernel's forward on TPU with no extra plumbing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BLOCKS = (512, 512)      # q_block, kv_block


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
               *, causal, window, q_block, kv_block, n_kv):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qpos = i * q_block + jax.lax.broadcasted_iota(
        jnp.int32, (q_block, kv_block), 0)
    kpos = j * kv_block + jax.lax.broadcasted_iota(
        jnp.int32, (q_block, kv_block), 1)
    valid = jnp.ones((q_block, kv_block), jnp.bool_)
    if causal:
        valid &= kpos <= qpos
    if window is not None:
        valid &= qpos - kpos < window
    bias = jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)

    q = q_ref[0].astype(jnp.float32)              # (qb, hd)
    k = k_ref[0].astype(jnp.float32)              # (kvb, hd)
    v = v_ref[0].astype(jnp.float32)
    logits = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) + bias

    # softmax state keeps a trailing unit dim ((qb, 1)) so every value
    # stays 2-D on the TPU's (sublane, lane) tiles
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, logits.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new)                   # (qb, kvb) — VMEM only
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0] = (m_ref[...] + jnp.log(l)).astype(lse_ref.dtype)


def flash_fwd_pallas(q, k, v, *, causal: bool, window=None,
                     q_block: int = DEFAULT_BLOCKS[0],
                     kv_block: int = DEFAULT_BLOCKS[1],
                     interpret: bool = False):
    """q: (B, S, KV, G, hd) pre-scaled; k/v: (B, S, KV, hd).
    Returns (out (B,S,KV,G,hd) f32, lse (B,KV,G,S) f32)."""
    B, S, KV, G, hd = q.shape
    assert S % q_block == 0 and S % kv_block == 0, (S, q_block, kv_block)
    nqb, nkv = S // q_block, S // kv_block
    qf = q.transpose(0, 2, 3, 1, 4).reshape(B * KV * G, S, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, S, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, S, hd)

    out, lse = pl.pallas_call(
        functools.partial(_fa_kernel, causal=causal, window=window,
                          q_block=q_block, kv_block=kv_block, n_kv=nkv),
        grid=(B * KV * G, nqb, nkv),
        in_specs=[
            pl.BlockSpec((1, q_block, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, kv_block, hd), lambda b, i, j, G=G: (b // G, j, 0)),
            pl.BlockSpec((1, kv_block, hd), lambda b, i, j, G=G: (b // G, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, q_block, hd), lambda b, i, j: (b, i, 0)),
            # lse as a (rows, S, 1) column: the block's last two dims are
            # (q_block, full) — tileable, unlike a (1, q_block) row
            pl.BlockSpec((1, q_block, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * KV * G, S, hd), jnp.float32),
            jax.ShapeDtypeStruct((B * KV * G, S, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((q_block, 1), jnp.float32),
            pltpu.VMEM((q_block, 1), jnp.float32),
            pltpu.VMEM((q_block, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_fwd_pallas",
        interpret=interpret,
    )(qf, kf, vf)

    out = out.reshape(B, KV, G, S, hd).transpose(0, 3, 1, 2, 4)
    lse = lse.reshape(B, KV, G, S)
    return out, lse
