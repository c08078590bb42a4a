"""Quantized einsum layers carrying the paper's per-bit indicator banks.

Every searchable projection is a param dict ``{"w", "s_w", "s_a"}`` where
``s_w``/``s_a`` are the (n_bits,) learnable scale banks — the layer's
importance indicators (paper §3.3/3.4). Bit selection is an *index into the
bank* so it can be static (ILP policy), uniform-traced (joint training pass
k), or random-traced (the communication pass), including under lax.scan.

Pinned 8-bit layers (embedding / lm head, paper §4.1) carry a single scale
and never enter the search.
"""
from __future__ import annotations

from dataclasses import dataclass
import jax
import jax.numpy as jnp

from repro.core.quantizer import (
    BitTables,
    bit_range,
    fake_quant,
    fake_quant_indexed,
    init_scale_from_stats,
    init_scale_same,
    lsq_grad_scale_factor,
)
from repro.models.common import dense_init

Array = jax.Array


@dataclass(frozen=True)
class QuantContext:
    """Static quantization-mode switches threaded through the model.

    ``kv_quant`` selects the decode-time KV-cache storage: "none" (fp),
    "int8" (codes + per-head write-time scales, ``runtime.kv_cache``), or
    "fake" (quantize-dequantize in an fp cache — the reference graph whose
    tokens the int8 path must reproduce exactly).
    """
    tables_w: BitTables
    tables_a: BitTables
    enabled: bool = True
    quantize_acts: bool = True
    compute_dtype: jnp.dtype = jnp.bfloat16
    kv_quant: str = "none"

    @staticmethod
    def make(bits, act_signed: bool, enabled: bool = True,
             compute_dtype=jnp.bfloat16, kv_quant: str = "none") -> "QuantContext":
        return QuantContext(
            tables_w=BitTables.make(bits, signed=True),
            tables_a=BitTables.make(bits, signed=act_signed),
            enabled=enabled,
            compute_dtype=compute_dtype,
            kv_quant=kv_quant,
        )

    @property
    def n_bits(self) -> int:
        return int(self.tables_w.bits.shape[0])


def fp_context(compute_dtype=jnp.bfloat16) -> QuantContext:
    """Quantization disabled (full-precision baseline)."""
    return QuantContext(
        tables_w=BitTables.make((8,), True),
        tables_a=BitTables.make((8,), True),
        enabled=False,
        compute_dtype=compute_dtype,
    )


# ---------------------------------------------------------------------------
# param construction
# ---------------------------------------------------------------------------
def qdense_init(rng, in_dim: int, out_dim: int, bits, *, stacked=()):
    """Searchable projection: weight + per-bit indicator banks.

    Weight scales use the paper's statistics init (2E|w|/sqrt(qmax_b));
    activation scales use the paper's same-value init 0.1/b (§3.3.2).
    Stacked layers (scan) get banks of shape (*stacked, n_bits).
    """
    w = dense_init(rng, in_dim, out_dim, stacked=stacked)
    s_w = jnp.stack(
        [init_scale_from_stats(w, bit_range(int(b), True)[1]) * jnp.ones(stacked)
         if stacked else init_scale_from_stats(w, bit_range(int(b), True)[1])
         for b in bits], axis=-1)
    s_a = jnp.stack(
        [init_scale_same(int(b)) * jnp.ones(stacked)
         if stacked else init_scale_same(int(b))
         for b in bits], axis=-1)
    return {"w": w, "s_w": jnp.asarray(s_w, jnp.float32),
            "s_a": jnp.asarray(s_a, jnp.float32)}


def pinned_init(rng, in_dim: int, out_dim: int, *, pinned_bits: int = 8,
                stacked=()):
    """8-bit pinned projection (embedding / lm head): single scale pair."""
    w = dense_init(rng, in_dim, out_dim, stacked=stacked)
    qmax = bit_range(pinned_bits, True)[1]
    s = init_scale_from_stats(w, qmax)
    if stacked:
        s = s * jnp.ones(stacked)
    return {"w": w, "s_w8": jnp.asarray(s, jnp.float32),
            "s_a8": jnp.full(stacked + (), 0.1 / pinned_bits, jnp.float32)}


# ---------------------------------------------------------------------------
# application
# ---------------------------------------------------------------------------
def _maybe_quant_w(p, w_idx, ctx: QuantContext) -> Array:
    w = p["w"]
    if ctx.enabled and w_idx is not None:
        w = fake_quant_indexed(w.astype(jnp.float32), p["s_w"], w_idx,
                               ctx.tables_w, numel=w.size)
    return w.astype(ctx.compute_dtype)


def _maybe_quant_a(x: Array, p, a_idx, ctx: QuantContext) -> Array:
    if ctx.enabled and ctx.quantize_acts and a_idx is not None:
        x = fake_quant_indexed(x, p["s_a"], a_idx, ctx.tables_a, numel=x.size)
    return x.astype(ctx.compute_dtype)


def qeinsum(eqn: str, x: Array, p, bits, ctx: QuantContext) -> Array:
    """Quantized einsum. `bits` is None (fp) or a dict {"w": idx, "a": idx}
    of scalar bank indices (python ints or traced).

    When `p` is a packed serving-time weight (``runtime.packing
    .PackedLinear``) instead of a fake-quant param dict, the matmul routes
    through the runtime kernel dispatch; the searched bit-widths are baked
    into the packed leaf, so `bits` is ignored."""
    if not isinstance(p, dict):
        from repro.runtime.dispatch import packed_qeinsum
        return packed_qeinsum(eqn, x, p, ctx)
    w_idx = None if bits is None else bits["w"]
    a_idx = None if bits is None else bits["a"]
    xq = _maybe_quant_a(x, p, a_idx, ctx)
    wq = _maybe_quant_w(p, w_idx, ctx)
    return row_einsum(eqn, xq, wq)


def qgrouped(x: Array, p, bits, group_sizes: Array, row_group: Array,
             ctx: QuantContext) -> Array:
    """Quantized grouped matmul of an expert stack: row m of ``x`` (M, K),
    rows sorted by expert (``group_sizes`` (E,) rows each), times the
    (K, N) slab of its expert ``row_group[m]`` of the stacked (E, K, N)
    weight. Each row's activation quantizes at its expert's bank step and
    each slab at its expert's, so the rows meet exactly the fake-quant
    operands of a per-expert einsum. A packed weight routes through
    ``runtime.dispatch.packed_grouped``."""
    if not isinstance(p, dict):
        from repro.runtime.dispatch import packed_grouped
        return packed_grouped(x, p, group_sizes, row_group, ctx)
    w_idx = None if bits is None else bits["w"]
    a_idx = None if bits is None else bits["a"]
    if ctx.enabled and ctx.quantize_acts and a_idx is not None:
        t = ctx.tables_a
        s = jnp.take(p["s_a"], a_idx, axis=-1)[row_group][:, None]
        g = lsq_grad_scale_factor(x.size, jnp.take(t.qmax, a_idx))
        x = fake_quant(x, s, jnp.take(t.qmin, a_idx).astype(x.dtype),
                       jnp.take(t.qmax, a_idx).astype(x.dtype),
                       grad_scale_factor=g)
    wq = _maybe_quant_w(p, w_idx, ctx)
    return jax.lax.ragged_dot(x.astype(ctx.compute_dtype), wq, group_sizes)


def row_einsum(eqn: str, x: Array, w: Array) -> Array:
    """``jnp.einsum`` for a projection whose output rows must not depend on
    how many rows ride along.

    XLA strips a one-row activation down to a vector-matrix product, which
    XLA:CPU (and only the CPU backend) runs through a GEMV kernel that sums
    in another order than its matrix kernel. A one-slot decode step would
    then write KV rows an ulp away from the rows a multi-token speculative
    verify writes for the same tokens. On the CPU a single row is padded to
    two along x's leading axis and sliced back, so every row count takes
    the matrix kernel; other backends run the einsum as written."""
    ins, out = eqn.split("->")
    lead = ins.split(",")[0][:1]
    if (jax.default_backend() != "cpu" or x.ndim < 2
            or x.size != x.shape[-1] or "." in eqn or lead not in out):
        return jnp.einsum(eqn, x, w)
    pad = jnp.concatenate([x, jnp.zeros_like(x)], axis=0)
    y = jnp.einsum(eqn, pad, w)
    return jax.lax.slice_in_dim(y, 0, 1, axis=out.index(lead))


def qeinsum_pinned(eqn: str, x: Array, p, ctx: QuantContext,
                   pinned_bits: int = 8, quant_act: bool = True) -> Array:
    """8-bit pinned einsum for first/last layers (outside the search)."""
    w = p["w"]
    if ctx.enabled:
        qmin, qmax = bit_range(pinned_bits, True)
        g = lsq_grad_scale_factor(w.size, qmax)
        w = fake_quant(w.astype(jnp.float32), p["s_w8"], qmin, qmax,
                       grad_scale_factor=g)
        if quant_act:
            ga = lsq_grad_scale_factor(x.size, qmax)
            x = fake_quant(x, p["s_a8"].astype(x.dtype), qmin, qmax,
                           grad_scale_factor=ga)
    return jnp.einsum(eqn, x.astype(ctx.compute_dtype),
                      w.astype(ctx.compute_dtype))


def embed_lookup_pinned(tokens: Array, p, ctx: QuantContext) -> Array:
    """Embedding table lookup with the table fake-quantized at 8 bits."""
    w = p["w"]
    if ctx.enabled:
        qmin, qmax = bit_range(8, True)
        g = lsq_grad_scale_factor(w.size, qmax)
        # a tied head fake-quantizes the same table: XLA computes it once,
        # under this scope
        with jax.named_scope("embed_fake_quant"):
            w = fake_quant(w.astype(jnp.float32), p["s_w8"], qmin, qmax,
                           grad_scale_factor=g)
    return jnp.take(w.astype(ctx.compute_dtype), tokens, axis=0)
