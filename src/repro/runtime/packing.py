"""Weight packing: searched-grid quantization + sub-8-bit bit-packing.

The storage half of executing an ILP-searched ``MPQPolicy``: every searched
projection is quantized onto its per-layer b-bit signed grid with the exact
rounding of the fake-quant training graph (``round(clip(w/s, qmin, qmax))``
with ``s = max(s, 1e-9)``), and the integer codes are bit-packed so HBM
holds ``ceil(n * b / 8)`` bytes — matching ``MPQPolicy.size_bytes`` to
within padding. Four storage layouts:

* ``int8``   — b == 8: codes stored as int8 in the weight's own shape.
* ``nib4``   — b == 4: two codes per byte along the contraction dim
               (``codes[k//2, n]``; low nibble = even k). This is the
               layout the ``kernels.quant_matmul.quant_matmul_w4``
               unpack-in-VMEM prologue consumes directly.
* ``quad2``  — b == 2: four codes per byte along the contraction dim.
* ``planes`` — any other b (3, 5, 6, 7): bit planes along the contraction
               dim. Bit ``j`` of the codes of rows ``8g .. 8g+7`` fills byte
               ``codes[j * ceil(K/8) + g, n]`` (bit ``s`` = row ``8g + s``):
               ``b`` plane blocks of ``ceil(K/8)`` rows each, one 2-D array
               for a 2-D weight, N on the lanes. The
               ``kernels.quant_matmul.quant_matmul_planes`` prologue unpacks
               it in VMEM.

Codes are stored offset-binary (``u = q - qmin``) so packed bytes are
unsigned; ``unpack_*`` restores the signed grid exactly (round-trip is
property-tested in tests/test_runtime.py for odd channel counts).

Tensor-parallel serving packs *per shard*: ``pack_linear(...,
shard_dim=d, shard_count=n)`` splits the weight into ``n`` equal shards
along its original tensor-parallel dim and packs each shard independently
(each padded to its own byte boundary), then concatenates the shard
layouts back along ``d``. The result is bit-identical, shard for shard, to
packing each shard on its own — so sharding ``codes`` over a mesh axis
hands every device exactly the packed slab it would have produced locally,
and per-device HBM is ``packed_bytes / shard_count`` (``per_shard_bytes``).
Only a shard dim that IS the packed contraction dim (row-parallel) changes
bytes: ``nib4``/``quad2`` when the per-shard row count is not a multiple of
the codes-per-byte, and ``planes`` always (each shard keeps its own plane
blocks). Everything else degenerates to the plain packing.

Scales are per-channel ``(out,)`` over the weight's last dim. The serving
session fills them with the trained per-tensor indicator-bank scale
broadcast per channel (bit-exact with the fake-quant graph); statistics
per-channel scales (``per_channel=True``) trade that exactness for lower
quantization error when no trained scale is available.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quantizer import bit_range

Array = jax.Array

SCALE_EPS = 1e-9  # fake_quant's scale floor — must match for bit-exactness


# ---------------------------------------------------------------------------
# kernel layouts (packed along the contraction dim, N on the lanes)
# ---------------------------------------------------------------------------
def _pad_rows(q: Array, mult: int) -> Array:
    k = q.shape[-2]
    pad = (-k) % mult
    if pad:
        width = [(0, 0)] * q.ndim
        width[-2] = (0, pad)
        q = jnp.pad(q, width)  # code 0 rows; offset applied after padding
    return q


def pack_nib4(q: Array) -> Array:
    """Signed int4 codes ``(..., K, N)`` -> ``(..., ceil(K/2), N)`` uint8,
    two per byte along K (low nibble = even k), offset-binary (q + 8)."""
    u = _pad_rows(jnp.asarray(q, jnp.int32) + 8, 2)
    lo = u[..., 0::2, :]
    hi = u[..., 1::2, :]
    return (lo | (hi << 4)).astype(jnp.uint8)


def unpack_nib4(codes: Array, k: int) -> Array:
    """Inverse of :func:`pack_nib4` -> ``(..., k, N)`` int8 codes."""
    c = jnp.asarray(codes, jnp.int32)
    lo = (c & 0xF) - 8
    hi = (c >> 4) - 8
    full = jnp.stack([lo, hi], axis=-2)              # (..., K2, 2, N)
    shape = full.shape[:-3] + (2 * c.shape[-2], c.shape[-1])
    return full.reshape(shape)[..., :k, :].astype(jnp.int8)


def pack_quad2(q: Array) -> Array:
    """Signed int2 codes ``(..., K, N)`` -> ``(..., ceil(K/4), N)`` uint8,
    four per byte along K, offset-binary (q + 2)."""
    u = _pad_rows(jnp.asarray(q, jnp.int32) + 2, 4)
    parts = [u[..., i::4, :] << (2 * i) for i in range(4)]
    return (parts[0] | parts[1] | parts[2] | parts[3]).astype(jnp.uint8)


def unpack_quad2(codes: Array, k: int) -> Array:
    """Inverse of :func:`pack_quad2` -> ``(..., k, N)`` int8 codes."""
    c = jnp.asarray(codes, jnp.int32)
    parts = [((c >> (2 * i)) & 0x3) - 2 for i in range(4)]
    full = jnp.stack(parts, axis=-2)                 # (..., K4, 4, N)
    shape = full.shape[:-3] + (4 * c.shape[-2], c.shape[-1])
    return full.reshape(shape)[..., :k, :].astype(jnp.int8)


def pack_planes(q: Array, bits: int) -> Array:
    """Signed ``bits``-wide codes ``(..., K, N)`` -> ``(..., bits *
    ceil(K/8), N)`` uint8 bit planes (module docstring), offset-binary.
    Works in bytes on ``(..., K/8, 8, N)`` views: no intermediate is wider
    than one byte a code."""
    qmin, _ = bit_range(bits, True)
    # pad rows hold the grid's zero; bits <= 7 keeps q - qmin inside int8
    u = (_pad_rows(jnp.asarray(q).astype(jnp.int8), 8) - qmin).astype(
        jnp.uint8)
    u = u.reshape(u.shape[:-2] + (-1, 8, u.shape[-1]))   # (..., K8, 8, N)
    shifts = jnp.arange(8, dtype=jnp.uint8)[:, None]
    planes = [jnp.sum(((u >> j) & 1) << shifts, axis=-2, dtype=jnp.uint8)
              for j in range(bits)]
    return jnp.concatenate(planes, axis=-2)


def unpack_planes(codes: Array, bits: int, k: int) -> Array:
    """Inverse of :func:`pack_planes` -> ``(..., k, N)`` int8 codes."""
    qmin, _ = bit_range(bits, True)
    c = jnp.asarray(codes, jnp.uint8)
    k8 = c.shape[-2] // bits
    c = c.reshape(c.shape[:-2] + (bits, k8, 1, c.shape[-1]))
    shifts = jnp.arange(8, dtype=jnp.uint8)[:, None]
    u = (c[..., 0, :, :, :] >> shifts) & 1             # (..., K8, 8, N)
    for j in range(1, bits):
        u = u | (((c[..., j, :, :, :] >> shifts) & 1) << j)
    u = u.reshape(u.shape[:-3] + (8 * k8, u.shape[-1]))[..., :k, :]
    return u.astype(jnp.int8) + jnp.int8(qmin)


def _layout_for(bits: int) -> str:
    return {8: "int8", 4: "nib4", 2: "quad2"}.get(bits, "planes")


_PACK_MULT = {"nib4": 2, "quad2": 4, "planes": 8}


def _pack(q: Array, layout: str, bits: int) -> Array:
    if layout == "nib4":
        return pack_nib4(q)
    if layout == "quad2":
        return pack_quad2(q)
    return pack_planes(q, bits)


def _unpack(codes: Array, layout: str, bits: int, k: int) -> Array:
    if layout == "nib4":
        return unpack_nib4(codes, k)
    if layout == "quad2":
        return unpack_quad2(codes, k)
    return unpack_planes(codes, bits, k)


def _split_shards(q: Array, dim: int, count: int):
    if q.shape[dim] % count:
        raise ValueError(
            f"shard dim {dim} of size {q.shape[dim]} does not split into "
            f"{count} equal shards")
    return jnp.split(q, count, axis=dim)


def _pack_sharded(q: Array, layout: str, bits: int, dim: int,
                  count: int) -> Array:
    """Pack each of ``count`` shards of ``q`` along ``dim`` independently
    (each pads its own rows to the layout's row multiple) and concatenate
    the per-shard layouts along ``dim``."""
    return jnp.concatenate([_pack(s, layout, bits)
                            for s in _split_shards(q, dim, count)], axis=dim)


# ---------------------------------------------------------------------------
# PackedLinear — the packed param-tree leaf
# ---------------------------------------------------------------------------
@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PackedLinear:
    """One searched projection in deployable form.

    ``codes``/``scale``/``s_a`` are pytree children (device arrays); the
    grid metadata is static aux data, so a jitted function closing over a
    packed param tree sees the bit-widths as compile-time constants —
    exactly what the unpack/dispatch code needs.
    """

    codes: Array                      # packed weight codes (layout-dependent)
    scale: Array                      # f32 dequant scale: (out,) per-channel
    #                                   or (E,1,1) per-expert broadcast form
    s_a: Array                        # f32 activation scale (trained bank):
    #                                   () scalar or (E,) per-expert
    w_bits: int = dataclasses.field(metadata=dict(static=True), default=8)
    a_bits: int = dataclasses.field(metadata=dict(static=True), default=8)
    a_signed: bool = dataclasses.field(metadata=dict(static=True), default=True)
    layout: str = dataclasses.field(metadata=dict(static=True), default="int8")
    shape: Tuple[int, ...] = dataclasses.field(metadata=dict(static=True),
                                               default=())
    per_channel: bool = dataclasses.field(metadata=dict(static=True),
                                          default=False)
    # tensor-parallel packing: the weight dim the codes were packed
    # per-shard along (None = plain packing) and the shard count. Static so
    # ``unpack`` can reassemble the per-shard layouts at trace time and
    # ``dist.sharding.packed_specs`` can tell a shardable layout from one
    # whose bytes would split mid-shard.
    shard_dim: Optional[int] = dataclasses.field(metadata=dict(static=True),
                                                 default=None)
    shard_count: int = dataclasses.field(metadata=dict(static=True),
                                         default=1)
    # activation-reuse group: projections with the same input and the same
    # (a_bits, a_signed, trained bank-scale values) share a tag, so the
    # dispatch layer quantizes their common activation once per forward
    # ("" = never reuse). Assigned by the serving session at pack time,
    # where the bank values are concrete and comparable.
    a_group: str = dataclasses.field(metadata=dict(static=True), default="")

    # -- accounting ---------------------------------------------------------
    @property
    def packed_bytes(self) -> int:
        """HBM bytes of the weight codes (scales reported separately)."""
        return int(np.prod(self.codes.shape)) * self.codes.dtype.itemsize

    @property
    def per_shard_bytes(self) -> int:
        """Per-device HBM bytes of the codes once sharded ``shard_count``
        ways (the full ``packed_bytes`` when packed unsharded/replicated).
        Exact — per-shard packing makes the sharded codes dim divisible."""
        return self.packed_bytes // max(self.shard_count, 1)

    @property
    def scale_bytes(self) -> int:
        return int(np.prod(self.scale.shape)) * self.scale.dtype.itemsize

    @property
    def a_range(self) -> Tuple[float, float]:
        lo, hi = bit_range(self.a_bits, self.a_signed)
        return float(lo), float(hi)

    # -- codes --------------------------------------------------------------
    def sharded_layout(self) -> bool:
        """True when the codes bytes differ from the plain packing — i.e.
        they are a concatenation of independently packed shard slabs that
        ``unpack`` must reassemble shard by shard. Only a row-parallel shard
        (the contraction dim) can break a layout: ``planes`` always keeps a
        shard's plane blocks together, ``nib4``/``quad2`` break where a
        shard's rows do not fill whole bytes."""
        if self.shard_count <= 1 or self.shard_dim is None:
            return False
        rank = len(self.shape)
        if self.layout not in _PACK_MULT or self.shard_dim % rank != rank - 2:
            return False
        return self.layout == "planes" or (
            self.shape[-2] // self.shard_count) % _PACK_MULT[self.layout] != 0

    @jax.named_scope("unpack")
    def unpack(self) -> Array:
        """Exact signed integer codes in the weight's original shape, under
        the named scope ``unpack`` (every route's unpack, traced)."""
        if self.layout == "int8":
            return self.codes
        if self.sharded_layout():
            return self._unpack_sharded()
        return _unpack(self.codes, self.layout, self.w_bits, self.shape[-2])

    def _unpack_sharded(self) -> Array:
        """Inverse of the per-shard packing along the contraction dim: split
        the codes into their ``shard_count`` slabs, unpack each, and
        concatenate."""
        ks = self.shape[-2] // self.shard_count
        slabs = jnp.split(self.codes, self.shard_count, axis=-2)
        return jnp.concatenate(
            [_unpack(s, self.layout, self.w_bits, ks) for s in slabs],
            axis=-2)

    def dequant(self, dtype=jnp.float32) -> Array:
        """Dequantized weight — bit-exact with the fake-quant graph when
        ``scale`` came from the trained indicator bank."""
        q = self.unpack().astype(jnp.float32)
        s = _broadcast_scale(self.scale, len(self.shape), self.shape)
        return (q * s).astype(dtype)


def _broadcast_scale(s: Array, w_ndim: int, w_shape) -> Array:
    """Align a scale against a weight: scalars broadcast plainly; a
    per-channel ``(out,)`` vector reshapes onto the LAST dim; anything of
    the weight's own rank (e.g. per-expert ``(E, 1, 1)``, already shaped
    like ``fake_quant_indexed``'s trailing-ones broadcast) passes through.
    """
    if s.ndim == 0:
        return s
    if s.ndim == w_ndim:
        return s
    if s.ndim == 1 and s.shape[0] == w_shape[-1]:
        return s.reshape((1,) * (w_ndim - 1) + (-1,))
    raise ValueError(f"scale shape {s.shape} does not align with weight "
                     f"shape {tuple(w_shape)}")


def quantize_to_grid(w: Array, bits: int, scale: Array) -> Array:
    """``round(clip(w/s, qmin, qmax))`` on the signed `bits` grid — the
    value map of ``core.quantizer.fake_quant`` (including its scale floor),
    so ``codes * s == fake_quant(w, s)`` exactly."""
    qmin, qmax = bit_range(bits, True)
    s = jnp.maximum(jnp.asarray(scale, jnp.float32), SCALE_EPS)
    s = _broadcast_scale(s, w.ndim, w.shape)
    return jnp.round(jnp.clip(w.astype(jnp.float32) / s, qmin, qmax))


def channel_scales(w: Array, bits: int) -> Array:
    """Statistics per-channel scales over the last (output) dim:
    ``max|w| / qmax`` reduced over every other axis."""
    _, qmax = bit_range(bits, True)
    red = tuple(range(w.ndim - 1))
    s = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=red) / float(qmax)
    return jnp.maximum(s, SCALE_EPS)


def pack_linear(w: Array, w_bits: int, s_w, a_bits: int, s_a, *,
                a_signed: bool = True,
                per_channel: bool = False,
                shard_dim: Optional[int] = None,
                shard_count: int = 1) -> PackedLinear:
    """Quantize ``w`` onto its searched grid and bit-pack the codes.

    ``s_w`` is the trained scale (the selected indicator-bank entry):
    a scalar for plain projections, or — for expert-stacked tensors whose
    banks select per expert — an array already shaped for the trailing-ones
    broadcast (e.g. ``(E, 1, 1)`` against ``(E, K, N)``). With
    ``per_channel=True`` it is ignored and statistics per-channel scales
    are computed instead (not bit-exact vs the trained fake-quant graph —
    see module docstring).

    ``shard_dim``/``shard_count`` request tensor-parallel per-shard packing
    (module docstring): the quantized codes are identical — only the byte
    layout changes, so each mesh shard of ``codes`` is exactly the packing
    of its weight shard. ``w.shape[shard_dim]`` must split evenly.
    """
    w = jnp.asarray(w)
    out = w.shape[-1]
    if per_channel:
        scale = channel_scales(w, w_bits)
    else:
        s = jnp.maximum(jnp.asarray(s_w, jnp.float32), SCALE_EPS)
        scale = jnp.broadcast_to(s.reshape(()), (out,)) if s.ndim == 0 \
            else s
    q = quantize_to_grid(w, w_bits, scale)
    layout = _layout_for(w_bits)
    sharded = shard_count > 1 and shard_dim is not None
    if sharded and w.shape[shard_dim] % shard_count:
        raise ValueError(
            f"shard dim {shard_dim} of weight shape {tuple(w.shape)} does "
            f"not split into {shard_count} shards")
    if layout == "int8":
        codes = q.astype(jnp.int8)   # byte-per-code: sharding never splits
    elif sharded and shard_dim % w.ndim == w.ndim - 2:
        codes = _pack_sharded(q, layout, w_bits, w.ndim - 2, shard_count)
    else:
        codes = _pack(q, layout, w_bits)
    return PackedLinear(
        codes=codes, scale=scale,
        s_a=jnp.asarray(s_a, jnp.float32),
        w_bits=int(w_bits), a_bits=int(a_bits), a_signed=bool(a_signed),
        layout=layout, shape=tuple(int(d) for d in w.shape),
        per_channel=bool(per_channel),
        shard_dim=(int(shard_dim) % w.ndim if sharded else None),
        shard_count=int(shard_count) if sharded else 1)


# ---------------------------------------------------------------------------
# tree-level accounting
# ---------------------------------------------------------------------------
def is_packed(leaf) -> bool:
    return isinstance(leaf, PackedLinear)


def packed_leaves(tree):
    return [x for x in jax.tree.leaves(tree, is_leaf=is_packed)
            if is_packed(x)]


def tree_packed_bytes(tree) -> int:
    """Measured HBM bytes of all packed weight codes in ``tree`` — the
    number the serve smoke checks against ``MPQPolicy.size_bytes``."""
    return sum(pl.packed_bytes for pl in packed_leaves(tree))


def tree_scale_bytes(tree) -> int:
    return sum(pl.scale_bytes for pl in packed_leaves(tree))


def tree_per_shard_bytes(tree) -> int:
    """Per-device HBM bytes of the packed codes under tensor-parallel
    sharding: sharded leaves contribute ``packed_bytes / shard_count``,
    replicated ones their full bytes — the number the per-chip memory gate
    checks against ``MPQPolicy.size_bytes(..., per_shard=tp)``."""
    return sum(pl.per_shard_bytes for pl in packed_leaves(tree))
