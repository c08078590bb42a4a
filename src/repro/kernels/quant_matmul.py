"""Int8 quantized matmul Pallas kernel with scale epilogue (TPU MXU target).

The serving-time execution of a searched policy: weights are pre-quantized
to the int8 grid (any searched bit-width b <= 8 lands on a subset of int8
codes), activations quantize on the fly, and the matmul runs int8 x int8 ->
int32 on the MXU — the TPU analog of the paper's low-bit GPU inference.
The epilogue applies `s_x * s_w` in VMEM, so HBM sees only int8 operands
and the f32 result.

Grid is (M/bm, N/bn, K/bk) with the K dimension sequential ("arbitrary"):
an f32 VMEM scratch accumulates partial products across K steps and the
epilogue fires on the last step. 128-aligned tiles keep the MXU full.

Numerics contract (tested): out == (q_x * s_x) @ (q_w * s_w) exactly in f32
for shapes where K * 127^2 < 2^31 (int32 accumulation, always true here).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCKS = (256, 256, 512)     # bm, bn, bk


def _qmm_kernel(x_ref, w_ref, sx_ref, sw_ref, o_ref, acc_ref, *, k_steps):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _epilogue():
        scale = sx_ref[0, 0] * sw_ref[0, 0]
        o_ref[...] = acc_ref[...].astype(jnp.float32) * scale


def _qmm_w4_kernel(x_ref, w_ref, sx_ref, sw_ref, o_ref, acc_ref, *, k_steps):
    """int8 x packed-int4 matmul: the weight block arrives as nib4 bytes
    (two K-rows per byte, offset-binary q+8) and unpacks in the VMEM
    prologue — HBM traffic for the weight is half the int8 kernel's."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    wp = w_ref[...].astype(jnp.int32)            # (bk//2, bn) nib4 bytes
    lo = (wp & 0xF) - 8
    hi = (wp >> 4) - 8
    bk2, bn = wp.shape
    w = jnp.stack([lo, hi], axis=1).reshape(2 * bk2, bn).astype(jnp.int8)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _epilogue():
        scale = sx_ref[0, 0] * sw_ref[0, 0]
        o_ref[...] = acc_ref[...].astype(jnp.float32) * scale


def quant_matmul_w4(x_q, w_p, s_x, s_w, *, k=None, blocks=DEFAULT_BLOCKS,
                    interpret: bool = False):
    """x_q: (M, K) int8; w_p: (K/2, N) uint8 nib4-packed int4 codes
    (``runtime.packing.pack_nib4`` layout); scalar scales -> (M, N) f32.

    ``k`` is the true contraction length (defaults to 2 * w_p.shape[0]);
    x_q columns beyond ``k`` must be absent. K must be even — odd
    contraction dims take the dequant-fp dispatch fallback.
    """
    M, K = x_q.shape
    K2, N = w_p.shape
    k = K if k is None else k
    assert k == K == 2 * K2, (x_q.shape, w_p.shape, k)
    bm, bn, bk = (min(blocks[0], M), min(blocks[1], N), min(blocks[2], K))
    bk += bk % 2
    pm, pn, pk = (-M) % bm, (-N) % bn, (-K) % bk
    if pm or pk:
        x_q = jnp.pad(x_q, ((0, pm), (0, pk)))   # zero codes: null products
    if pk or pn:
        # pad bytes are 0x88 = two offset-binary zeros (plain 0x00 would
        # decode to q = -8 rows; harmless only because x pads are zero —
        # keep the buffer self-consistent anyway)
        w_p = jnp.pad(w_p, ((0, pk // 2), (0, pn)), constant_values=0x88)
    Mp, Kp = x_q.shape
    Np = w_p.shape[1]
    k_steps = Kp // bk
    grid = (Mp // bm, Np // bn, k_steps)

    out = pl.pallas_call(
        functools.partial(_qmm_w4_kernel, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk // 2, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        name="quant_matmul_w4",
        interpret=interpret,
    )(x_q, w_p, s_x.reshape(1, 1), s_w.reshape(1, 1))
    return out[:M, :N]


def quant_matmul(x_q, w_q, s_x, s_w, blocks=DEFAULT_BLOCKS,
                 interpret: bool = False):
    """x_q: (M, K) int8; w_q: (K, N) int8; s_x/s_w scalar f32 -> (M, N) f32."""
    M, K = x_q.shape
    K2, N = w_q.shape
    assert K == K2, (x_q.shape, w_q.shape)
    bm, bn, bk = (min(blocks[0], M), min(blocks[1], N), min(blocks[2], K))
    pm, pn, pk = (-M) % bm, (-N) % bn, (-K) % bk
    if pm or pk:
        x_q = jnp.pad(x_q, ((0, pm), (0, pk)))
    if pk or pn:
        w_q = jnp.pad(w_q, ((0, pk), (0, pn)))
    Mp, Kp = x_q.shape
    Np = w_q.shape[1]
    k_steps = Kp // bk
    grid = (Mp // bm, Np // bn, k_steps)

    out = pl.pallas_call(
        functools.partial(_qmm_kernel, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        name="quant_matmul",
        interpret=interpret,
    )(x_q, w_q, s_x.reshape(1, 1), s_w.reshape(1, 1))
    return out[:M, :N]
