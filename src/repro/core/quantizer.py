"""LSQ-style uniform quantizer with learnable step-size scale factors.

This is Eq. (1) of the paper:

    v_q = round(clip(v / s, min_b, max_b)) * s

with the LSQ straight-through gradients (Esser et al., ICLR'20): the round is
an STE, and d v_q / d s is `round(v/s) - v/s` inside the clip range and
`min_b` / `max_b` outside — obtained here *compositionally* from two STE
primitives (``round_ste`` on top of ``clip``), which yields exactly the LSQ
vjp (see tests/test_quantizer.py::test_lsq_scale_gradient).

The paper's central object — the *importance indicator* — is the learned
scale `s` itself, kept **per bit-width** in an ``IndicatorBank`` so that one
joint QAT run learns all `2 * L * n` indicators at once (paper §3.4).
"""
from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp

Array = jax.Array


def round_ste(x: Array) -> Array:
    """round() with a straight-through gradient."""
    return x + jax.lax.stop_gradient(jnp.round(x) - x)


def grad_scale(x: Array, scale) -> Array:
    """Identity in value; gradient multiplied by `scale` (LSQ trick).

    The value is ``x`` plus an exact zero, so it is bitwise ``x`` whatever
    ``scale`` rounds to: the same scale factor computed in two graphs (a
    constant-folded one and a runtime one) yields one quantization grid."""
    xs = x * scale
    return jax.lax.stop_gradient(x) + (xs - jax.lax.stop_gradient(xs))


def bit_range(b, signed: bool):
    """(qmin, qmax) for bit-width `b`. Works on python ints and traced arrays."""
    if signed:
        return -(2 ** (b - 1)), 2 ** (b - 1) - 1
    return 0 if not isinstance(b, jnp.ndarray) else jnp.zeros_like(b), 2 ** b - 1


def fake_quant(v: Array, s: Array, qmin, qmax, *, grad_scale_factor=None) -> Array:
    """Quantize-dequantize `v` with scale `s` (Eq. 1) and LSQ gradients.

    `qmin`/`qmax` may be python scalars or traced scalars (dynamic bit-width
    during joint importance training). `s` is a per-tensor scalar.
    """
    s = jnp.maximum(jnp.asarray(s, v.dtype), jnp.asarray(1e-9, v.dtype))
    if grad_scale_factor is not None:
        s = grad_scale(s, jnp.asarray(grad_scale_factor, v.dtype))
    vs = v / s
    vbar = jnp.clip(vs, qmin, qmax)
    return round_ste(vbar) * s


def lsq_grad_scale_factor(numel: int, qmax) -> Array:
    """LSQ gradient normalizer g = 1 / sqrt(numel * qmax). `numel` goes in
    as python float — giant activation tensors overflow int32 otherwise."""
    return 1.0 / jnp.sqrt(jnp.maximum(
        float(numel) * jnp.asarray(qmax, jnp.float32), 1.0))


def init_scale_from_stats(v: Array, qmax) -> Array:
    """LSQ statistics init: s0 = 2*E|v| / sqrt(qmax) (paper §3.3.2 keeps it)."""
    return 2.0 * jnp.mean(jnp.abs(v.astype(jnp.float32))) / jnp.sqrt(
        jnp.asarray(qmax, jnp.float32)
    )


def init_scale_same(b) -> Array:
    """Paper's alternative same-value init: s_b = 0.1 / b (§3.3.2)."""
    return 0.1 / jnp.asarray(b, jnp.float32)


class BitTables(NamedTuple):
    """Static per-bit (qmin, qmax, grad-scale-vs-qmax) lookup tables so a
    *traced* bit index can select its range with a gather."""
    bits: Array     # (n,) int32
    qmin: Array     # (n,) float32
    qmax: Array     # (n,) float32

    @staticmethod
    def make(bits: Sequence[int], signed: bool) -> "BitTables":
        qmins, qmaxs = [], []
        for b in bits:
            lo, hi = bit_range(int(b), signed)
            qmins.append(float(lo))
            qmaxs.append(float(hi))
        return BitTables(
            bits=jnp.asarray(bits, jnp.int32),
            qmin=jnp.asarray(qmins, jnp.float32),
            qmax=jnp.asarray(qmaxs, jnp.float32),
        )


def fake_quant_indexed(
    v: Array,
    scale_bank: Array,     # (n_bits,) learnable indicator bank for this tensor
    bit_idx,               # scalar int (python or traced): index into the bank
    tables: BitTables,
    numel: int,
) -> Array:
    """Fake-quant `v` at the bank entry `bit_idx`.

    This is the joint-training workhorse: uniform-bit passes feed the same
    `bit_idx` to every layer, the random pass feeds per-layer indices, and
    policy execution feeds the ILP-chosen static index. Only the selected
    bank entry receives gradient (gather has scatter-add transpose).

    `scale_bank` may carry leading stacked dims, e.g. (E, n) for MoE expert
    stacks — the selected scale then broadcasts per-expert against `v`.
    """
    s = jnp.take(scale_bank, bit_idx, axis=-1)
    if s.ndim:                       # (E,) -> (E, 1, ..., 1) to broadcast
        s = s.reshape(s.shape + (1,) * (v.ndim - s.ndim))
    qmin = jnp.take(tables.qmin, bit_idx).astype(v.dtype)
    qmax = jnp.take(tables.qmax, bit_idx).astype(v.dtype)
    g = lsq_grad_scale_factor(numel, jnp.take(tables.qmax, bit_idx))
    return fake_quant(v, s, qmin, qmax, grad_scale_factor=g)


def quantization_error(v: Array, s: Array, qmin, qmax) -> Array:
    """||Q(v) - v||^2 — used by the HAWQ-style baseline's sensitivity metric."""
    q = fake_quant(v, s, qmin, qmax)
    d = (q - v).astype(jnp.float32)
    return jnp.sum(d * d)
