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

Two variants take packed weight bytes and unpack them in VMEM, so HBM sees
the packed bytes only: ``quant_matmul_w4`` (``nib4``, two codes a byte) and
``quant_matmul_planes`` (``planes``, b bit planes of 8 codes a byte, for
b = 3, 5, 6, 7).

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


def _qmm_planes_kernel(x_ref, *refs, bits, k_steps):
    """int8 x bit-plane matmul: the weight block arrives as ``bits`` plane
    blocks of ``(bk/8, bn)`` bytes (``runtime.packing.pack_planes``; bit s
    of a byte = K-row 8g + s) and unpacks in the VMEM prologue, plane rows
    regrouped by bit position: unpacked row ``s * bk/8 + g`` is K-row
    ``8g + s``, the order the wrapper gave the activation's columns."""
    plane_refs = refs[:bits]
    sx_ref, sw_ref, o_ref, acc_ref = refs[bits:]

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    planes = [r[...].astype(jnp.int32) for r in plane_refs]
    bk8, bn = planes[0].shape
    # one (8, bk/8, bn) pass a plane: leading index s takes bit s
    s = jax.lax.broadcasted_iota(jnp.int32, (8, bk8, bn), 0)
    u = functools.reduce(jnp.bitwise_or, [((p[None] >> s) & 1) << j
                                          for j, p in enumerate(planes)])
    qmin = -(1 << (bits - 1))                    # codes are offset-binary
    w = (u.reshape(8 * bk8, bn) + qmin).astype(jnp.int8)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _epilogue():
        scale = sx_ref[0, 0] * sw_ref[0, 0]
        o_ref[...] = acc_ref[...].astype(jnp.float32) * scale


def _tile(dim: int, cap: int, quantum: int):
    """The largest multiple of ``quantum`` up to ``cap`` dividing ``dim``."""
    t = min(cap, dim) // quantum * quantum
    while t > 0 and dim % t:
        t -= quantum
    return t or None


# a TPU tiles one-byte arrays (32, 128): a plane block of bk/8 rows needs bk
# a multiple of 256, and bn of 128
_PLANES_QUANTA = (256, 128)


def planes_tiles(k: int, n: int, blocks=DEFAULT_BLOCKS):
    """(bk, bn) of the planes kernel for a (k, n) weight: bk divides K
    padded to whole 8-row groups, bn divides N, so the plane bytes are never
    padded per call. Multiples of the TPU's one-byte tile where such tiles
    exist, else of 8 rows and 1 lane (the interpreter takes any)."""
    kp = 8 * -(-k // 8)
    bk = _tile(kp, blocks[2], _PLANES_QUANTA[0]) or _tile(kp, blocks[2], 8)
    bn = _tile(n, blocks[1], _PLANES_QUANTA[1]) or _tile(n, blocks[1], 1)
    return bk, bn


def planes_tiled(k: int, n: int) -> bool:
    """True where the planes kernel's tiles for a (k, n) weight fit the
    TPU's one-byte tile."""
    bk, bn = planes_tiles(k, n)
    return bk % _PLANES_QUANTA[0] == 0 and bn % _PLANES_QUANTA[1] == 0


def quant_matmul_planes(x_q, w_p, s_x, s_w, *, bits: int,
                        blocks=DEFAULT_BLOCKS, interpret: bool = False):
    """x_q: (M, K) int8; w_p: (bits * ceil(K/8), N) uint8 bit planes
    (``runtime.packing.pack_planes`` layout); scalar scales -> (M, N) f32.

    The same 2-D plane array is every weight operand, one BlockSpec per
    plane. Within each K tile the activation's columns are regrouped by
    bit position to match the order the kernel unpacks weight rows in; the
    weight bytes are used as stored.
    """
    M, K = x_q.shape
    rows, N = w_p.shape
    kp = 8 * -(-K // 8)
    assert rows == bits * (kp // 8), (x_q.shape, w_p.shape, bits)
    bk, bn = planes_tiles(K, N, blocks)
    bm = min(blocks[0], M)
    pm = (-M) % bm
    if pm or kp > K:
        x_q = jnp.pad(x_q, ((0, pm), (0, kp - K)))   # zero codes
    Mp = M + pm
    x_q = x_q.reshape(Mp, kp // bk, bk // 8, 8).swapaxes(2, 3).reshape(
        Mp, kp)
    k_steps = kp // bk
    grid = (Mp // bm, N // bn, k_steps)
    planes = [pl.BlockSpec((bk // 8, bn),
                           lambda i, j, kk, b=b: (b * k_steps + kk, j))
              for b in range(bits)]

    out = pl.pallas_call(
        functools.partial(_qmm_planes_kernel, bits=bits, k_steps=k_steps),
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk))]
        + planes + [
            pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        name="quant_matmul_planes",
        interpret=interpret,
    )(x_q, *([w_p] * bits), s_x.reshape(1, 1), s_w.reshape(1, 1))
    return out[:M]


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
