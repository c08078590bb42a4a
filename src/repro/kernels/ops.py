"""Jitted public wrappers around the Pallas kernels.

On CPU (this container) kernels run with interpret=True — the kernel body
executes in Python, validating the exact TPU program logic. On a real TPU
backend `interpret` flips to False automatically.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import fake_quant as _fq
from repro.kernels import quant_matmul as _qmm
from repro.kernels import rwkv_scan as _wkv


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# fake_quant with LSQ custom_vjp
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def fake_quant(v, s, qmin: float, qmax: float, grad_scale: float = 1.0):
    """Fused LSQ fake-quant on an arbitrary-shape tensor (flattened 2D)."""
    return _fq_fwd(v, s, qmin, qmax, grad_scale)[0]


def _as2d(v):
    if v.ndim == 1:
        return v.reshape(1, -1)
    return v.reshape(-1, v.shape[-1])


def _fq_fwd(v, s, qmin, qmax, grad_scale):
    out2d = _fq.fake_quant_fwd(_as2d(v), s.astype(jnp.float32), qmin, qmax,
                               interpret=_interpret_default())
    return out2d.reshape(v.shape), (v, s)


def _fq_bwd(qmin, qmax, grad_scale, res, g):
    v, s = res
    dv2d, ds_part = _fq.fake_quant_bwd(_as2d(v), s.astype(jnp.float32),
                                       _as2d(g), qmin, qmax,
                                       interpret=_interpret_default())
    ds = jnp.sum(ds_part) * grad_scale
    return dv2d.reshape(v.shape), ds.astype(s.dtype)


fake_quant.defvjp(_fq_fwd, _fq_bwd)


# ---------------------------------------------------------------------------
# quant_matmul
# ---------------------------------------------------------------------------
def quant_matmul(x_q, w_q, s_x, s_w, blocks=_qmm.DEFAULT_BLOCKS):
    """(M,K) int8 x (K,N) int8 -> (M,N) f32 with per-tensor scale epilogue."""
    return _qmm.quant_matmul(x_q, w_q, jnp.asarray(s_x, jnp.float32),
                             jnp.asarray(s_w, jnp.float32), blocks=blocks,
                             interpret=_interpret_default())


def quant_matmul_w4(x_q, w_p, s_x, s_w, *, k=None, blocks=_qmm.DEFAULT_BLOCKS):
    """(M,K) int8 x nib4-packed (K/2,N) uint8 int4 weights -> (M,N) f32.
    The weight nibbles unpack in the kernel's VMEM prologue."""
    return _qmm.quant_matmul_w4(x_q, w_p, jnp.asarray(s_x, jnp.float32),
                                jnp.asarray(s_w, jnp.float32), k=k,
                                blocks=blocks,
                                interpret=_interpret_default())


def quant_matmul_planes(x_q, w_p, s_x, s_w, *, bits: int,
                        blocks=_qmm.DEFAULT_BLOCKS):
    """(M,K) int8 x (bits*ceil(K/8),N) uint8 bit planes -> (M,N) f32.
    The weight's bit planes unpack in the kernel's VMEM prologue."""
    return _qmm.quant_matmul_planes(x_q, w_p, jnp.asarray(s_x, jnp.float32),
                                    jnp.asarray(s_w, jnp.float32), bits=bits,
                                    blocks=blocks,
                                    interpret=_interpret_default())


def quantize_int8(v, s, bits: int = 8):
    """Round v/s to the signed `bits`-wide integer grid, stored as int8."""
    qmax = 2 ** (bits - 1) - 1
    qmin = -(2 ** (bits - 1))
    return jnp.clip(jnp.round(v / s), qmin, qmax).astype(jnp.int8)


# ---------------------------------------------------------------------------
# fused int8 decode attention
# ---------------------------------------------------------------------------
def decode_attn_quant(q, k_codes, k_scale, v_codes, v_scale, pos_arr, q_pos,
                      *, window=None, interpret=None):
    """One-token decode attention directly on int8 KV codes + f32 scales
    (no HBM-resident dequantized cache). ``interpret=None`` follows the
    backend; the ``fused-interpret`` dispatch route pins it True."""
    from repro.kernels import quant_attention as _qa
    if interpret is None:
        interpret = _interpret_default()
    return _qa.decode_attn_quant(q, k_codes, k_scale, v_codes, v_scale,
                                 pos_arr, q_pos, window=window,
                                 interpret=interpret)


def decode_attn_quant_paged(q, k_pages, k_scale, v_pages, v_scale, page_pos,
                            page_table, q_pos, *, window=None,
                            interpret=None):
    """One-token decode attention over the paged int8 KV layout: the page
    table rides in as a scalar-prefetch operand and blocks gather by page
    index (see ``kernels.quant_attention.decode_attn_quant_paged``)."""
    from repro.kernels import quant_attention as _qa
    if interpret is None:
        interpret = _interpret_default()
    return _qa.decode_attn_quant_paged(q, k_pages, k_scale, v_pages, v_scale,
                                       page_pos, page_table, q_pos,
                                       window=window, interpret=interpret)


def verify_attn_quant(q, k_codes, k_scale, v_codes, v_scale, pos_arr, q_pos,
                      *, window=None, interpret=None):
    """S-token speculative-verify attention on int8 KV codes: unrolled onto
    the exact one-token kernel program per query position (see
    ``kernels.quant_attention.verify_attn_quant`` for why the unroll is
    the bitwise-identity contract)."""
    from repro.kernels import quant_attention as _qa
    if interpret is None:
        interpret = _interpret_default()
    return _qa.verify_attn_quant(q, k_codes, k_scale, v_codes, v_scale,
                                 pos_arr, q_pos, window=window,
                                 interpret=interpret)


def verify_attn_quant_paged(q, k_pages, k_scale, v_pages, v_scale, page_pos,
                            page_table, q_pos, *, window=None,
                            interpret=None):
    """S-token speculative-verify attention over the paged int8 KV layout
    (``kernels.quant_attention.verify_attn_quant_paged``)."""
    from repro.kernels import quant_attention as _qa
    if interpret is None:
        interpret = _interpret_default()
    return _qa.verify_attn_quant_paged(q, k_pages, k_scale, v_pages, v_scale,
                                       page_pos, page_table, q_pos,
                                       window=window, interpret=interpret)


# ---------------------------------------------------------------------------
# rwkv wkv
# ---------------------------------------------------------------------------
def wkv(r, k, v, log_w, u, chunk: int = _wkv.DEFAULT_CHUNK):
    """Chunked wkv recurrence from zero state. (B,S,H,hd) -> (B,S,H,hd) f32."""
    return _wkv.wkv_pallas(r, k, v, log_w, u, chunk=chunk,
                           interpret=_interpret_default())


# ---------------------------------------------------------------------------
# flash attention forward
# ---------------------------------------------------------------------------
def flash_fwd(q, k, v, *, causal: bool, window=None, q_block: int = 512,
              kv_block: int = 512):
    """Online-softmax attention forward with VMEM-resident state.
    q: (B,S,KV,G,hd) pre-scaled; returns (out, lse)."""
    from repro.kernels import flash_attention as _fa
    return _fa.flash_fwd_pallas(q, k, v, causal=causal, window=window,
                                q_block=q_block, kv_block=kv_block,
                                interpret=_interpret_default())
