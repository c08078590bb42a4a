"""Fused LSQ fake-quant Pallas kernel (TPU target, validated interpret=True).

XLA lowers Eq. 1 (`round(clip(v/s)) * s`) plus the LSQ backward into several
elementwise HBM round-trips; memory-bound at ~3x the minimum traffic. The
kernel fuses forward into ONE VMEM pass, and the backward (dv, partial ds)
into one more. Tiles are (block_rows, 128·lanes) — VPU-aligned.

The scalar step size `s` rides along as a (1, 1) block broadcast to every
tile; ds is reduced hierarchically: each tile writes one partial, the (tiny)
final sum happens in the jitted wrapper (`ops.fake_quant`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK = (256, 512)


def _fwd_kernel(v_ref, s_ref, o_ref, *, qmin, qmax):
    s = jnp.maximum(s_ref[0, 0], 1e-9)
    vs = v_ref[...].astype(jnp.float32) / s
    vbar = jnp.clip(vs, qmin, qmax)
    o_ref[...] = (jnp.round(vbar) * s).astype(o_ref.dtype)


def _bwd_kernel(v_ref, s_ref, g_ref, dv_ref, ds_ref, *, qmin, qmax):
    s = jnp.maximum(s_ref[0, 0], 1e-9)
    vs = v_ref[...].astype(jnp.float32) / s
    g = g_ref[...].astype(jnp.float32)
    inside = (vs > qmin) & (vs < qmax)
    # dv: straight-through inside the clip range
    dv_ref[...] = jnp.where(inside, g, 0.0).astype(dv_ref.dtype)
    # ds: (round(vs) - vs) inside; clip boundary outside
    r = jnp.round(jnp.clip(vs, qmin, qmax))
    dsd = jnp.where(inside, r - vs, jnp.clip(vs, qmin, qmax))
    ds_ref[0, 0] = jnp.sum(g * dsd)


def _pad2d(v, bm, bn):
    M, N = v.shape
    pm, pn = (-M) % bm, (-N) % bn
    if pm or pn:
        v = jnp.pad(v, ((0, pm), (0, pn)))
    return v


def fake_quant_fwd(v2d, s, qmin: float, qmax: float,
                   block=DEFAULT_BLOCK, interpret: bool = False):
    """v2d: (M, N) f32; s: scalar f32. Returns quant-dequant of v2d."""
    M, N = v2d.shape
    bm, bn = min(block[0], M), min(block[1], N)
    vp = _pad2d(v2d, bm, bn)
    Mp, Np = vp.shape
    grid = (Mp // bm, Np // bn)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, qmin=qmin, qmax=qmax),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), v2d.dtype),
        name="fake_quant_fwd",
        interpret=interpret,
    )(vp, s.reshape(1, 1))
    return out[:M, :N]


def fake_quant_bwd(v2d, s, g2d, qmin: float, qmax: float,
                   block=DEFAULT_BLOCK, interpret: bool = False):
    """Returns (dv (M,N), ds_partials (grid_m, grid_n))."""
    M, N = v2d.shape
    bm, bn = min(block[0], M), min(block[1], N)
    vp, gp = _pad2d(v2d, bm, bn), _pad2d(g2d, bm, bn)
    Mp, Np = vp.shape
    grid = (Mp // bm, Np // bn)
    dv, ds = pl.pallas_call(
        functools.partial(_bwd_kernel, qmin=qmin, qmax=qmax),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Mp, Np), v2d.dtype),
            jax.ShapeDtypeStruct(grid, jnp.float32),
        ],
        name="fake_quant_bwd",
        interpret=interpret,
    )(vp, s.reshape(1, 1), gp)
    return dv[:M, :N], ds
