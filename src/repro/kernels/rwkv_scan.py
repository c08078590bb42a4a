"""Chunked RWKV6 wkv recurrence as a Pallas kernel (TPU target).

The wkv recurrence is the sequential hot loop of the rwkv6 arch — the one
assigned architecture whose core compute is NOT a plain matmul. The pure-JAX
chunked form (repro.models.recurrent.wkv_chunked) materializes a
(B, H, T, T, hd) decay tensor per chunk in HBM; this kernel keeps everything
for one (batch*head, chunk) tile in VMEM:

  grid = (B*H parallel, n_chunks sequential)
  state (hd, hd) f32 lives in a VMEM scratch that persists across the
  sequential chunk dimension — the TPU-idiomatic replacement for a
  carried-scan in HBM.

Math identical to wkv_chunked (exponents of non-positive numbers only).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 32


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, y_ref, s_ref, *, chunk):
    T = chunk

    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[0].astype(jnp.float32)        # (T, hd)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)        # (1, hd) broadcast row
    S0 = s_ref[...]                         # (hd, hd)

    L = jnp.cumsum(lw, axis=0)              # inclusive
    Lx = L - lw                             # exclusive

    # inter-chunk contribution
    r_in = r * jnp.exp(Lx)
    y = jnp.dot(r_in, S0, preferred_element_type=jnp.float32)

    # intra-chunk strict-causal pairs (exponents <= 0 by construction)
    expo = Lx[:, None, :] - L[None, :, :]               # (t, tau, hd)
    tri = (jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
           > jax.lax.broadcasted_iota(jnp.int32, (T, T), 1))
    dec = jnp.exp(jnp.minimum(expo, 0.0)) * tri[..., None]
    A = jnp.einsum("ti,tsi,si->ts", r, dec, k)          # (T, T)
    y += jnp.dot(A, v, preferred_element_type=jnp.float32)

    # bonus diagonal
    y += jnp.sum(r * (u * k), axis=-1, keepdims=True) * v

    # state update
    LT = L[-1:]                                          # (1, hd)
    k_dec = k * jnp.exp(LT - L)
    s_ref[...] = jnp.exp(LT).T * S0 + jnp.dot(
        k_dec.T, v, preferred_element_type=jnp.float32)
    y_ref[0] = y.astype(y_ref.dtype)


def wkv_pallas(r, k, v, log_w, u, chunk: int = DEFAULT_CHUNK,
               interpret: bool = False):
    """r/k/v/log_w: (B, S, H, hd); u: (H, hd). Returns y (B, S, H, hd) f32.

    Zero initial state (training/prefill-from-scratch semantics; carried
    state across calls is handled by the pure-JAX wrapper in models).
    """
    B, S, H, hd = r.shape
    assert S % chunk == 0, (S, chunk)
    n_chunks = S // chunk

    def to_bh(a):
        return a.transpose(0, 2, 1, 3).reshape(B * H, S, hd)

    rb, kb, vb, lwb = map(to_bh, (r, k, v, log_w))
    ub = jnp.broadcast_to(u[None], (B, H, hd)).reshape(B * H, 1, hd)

    y = pl.pallas_call(
        functools.partial(_wkv_kernel, chunk=chunk),
        grid=(B * H, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, hd), lambda b, c: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="wkv_pallas",
        interpret=interpret,
    )(rb, kb, vb, lwb, ub)
    return y.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
