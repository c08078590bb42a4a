"""Per-arch partition rules with divisibility fallbacks.

``make_axes_for`` resolves the logical axes of ``MeshAxes`` against a
concrete mesh: an axis is only assigned when the arch's dimension divides
the mesh axis size, otherwise it falls back to replication (e.g.
starcoder2's 36 heads don't divide a 16-wide model axis -> attention runs
replicated while the MLPs still shard).

``param_spec_fn`` encodes the megatron layout:

  column-parallel (out-dim sharded):  wq wk wv · mlp_wi mlp_wg · rwkv
      wr/wk/wv/wg/cm_wk/cm_wr · rg wx/wgate · shared_wi shared_wg
  row-parallel (in-dim sharded):      wo · mlp_wo · cm_wv · rg wo ·
      shared_wo
  expert-parallel:                    moe wi/wg/wo on the expert dim, or
      on the per-expert d_ff dim when n_experts doesn't divide (mixtral)
  vocab-parallel:                     embed (dim 0) and head (dim -1)
  replicated:                         scale banks, norms, router, gates,
      mixing/decay tables — everything that is not a projection weight

Every rule re-checks divisibility against the actual tensor dim, so the
emitted specs are always valid for the mesh (tests/test_sharding.py
asserts this for every arch).
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.dist.axes import Axes, MeshAxes

# Projection classification by the weight's parent key in the param tree.
_ATTN_CORE = {"wq", "wk", "wv", "wo"}
_COL = {"wq", "wk", "wv", "wr", "wg", "wx", "wgate",
        "mlp_wi", "mlp_wg", "shared_wi", "shared_wg", "cm_wk", "cm_wr"}
_ROW = {"wo", "mlp_wo", "shared_wo", "cm_wv"}


def _axis_sizes(mesh) -> dict:
    return {k: int(v) for k, v in dict(mesh.shape).items()}


def make_axes_for(cfg: ModelConfig, mesh, shard_seq="auto") -> MeshAxes:
    """Resolve logical axes for (cfg, mesh) with divisibility fallbacks.

    ``mesh`` needs only ``axis_names`` and ``shape`` (tests use a pure
    stand-in; specs are shape arithmetic, not device state).

    ``shard_seq``: "auto"/True enables sequence parallelism over the
    model axis; False keeps sequence dims replicated (exact-numerics
    comparisons against single-device execution).
    """
    names = tuple(mesh.axis_names)
    sizes = _axis_sizes(mesh)
    tp: Axes = ("model",) if "model" in names else ()
    tp_size = sizes.get("model", 1)
    dp: Axes = tuple(n for n in names if n != "model")
    dp_size = int(np.prod([sizes[n] for n in dp])) if dp else 1

    def fits(dim: int) -> Axes:
        return tp if (tp and dim % tp_size == 0) else ()

    ep: Axes = ()
    mtp: Axes = ()
    if cfg.moe and cfg.moe.n_experts:
        ep = fits(cfg.moe.n_experts)
        if not ep:                       # mixtral: 8 experts vs 16-wide axis
            mtp = fits(cfg.moe.d_ff)

    return MeshAxes(
        mesh=mesh,
        dp=dp,
        sp=tp if (shard_seq and tp) else (),
        tp=tp,
        th=fits(cfg.n_heads),
        tv=fits(cfg.vocab),
        ep=ep,
        mtp=mtp,
        dp_size=dp_size,
        tp_size=tp_size,
    )


# ---------------------------------------------------------------------------
# parameter partition rules
# ---------------------------------------------------------------------------
def _replicate(rank: int) -> P:
    return P(*([None] * rank))


def _shard_dim(rank: int, dim: int, ax: Axes) -> P:
    entries = [None] * rank
    entries[dim % rank] = ax
    return P(*entries)


def param_spec_fn(cfg: ModelConfig,
                  axes: MeshAxes) -> Callable[[str, Tuple[int, ...]], P]:
    """Returns ``fn(param_name, shape) -> PartitionSpec``.

    ``param_name`` is the '/'-joined tree path ("body/0/wq/w"). Only
    leaves named "w" are projection weights; every other leaf (scale
    banks, norms, gates, mixing tables) replicates.

    Packed serving-time weights (``runtime.packing.PackedLinear``) do NOT
    route through this fn directly — ``packed_specs`` maps each packed
    leaf's *original* projection rule (looked up here under the synthetic
    "/w" name) onto its packed code/scale layout, and
    ``projection_shard_fn`` feeds the same rule to shard-aware packing so
    the sharded codes split on per-shard byte boundaries. The int8 KV
    cache needs no rule here — ``decode_state_specs`` shards its
    code/scale slot axis like any other decode-state leaf.
    """
    tps = axes.tp_size

    def ok(shape, dim: int, ax: Axes) -> bool:
        return bool(ax) and shape[dim] % tps == 0

    def fn(name: str, shape: Tuple[int, ...]) -> P:
        parts = name.split("/")
        rank = len(shape)
        rep = _replicate(rank)
        if parts[-1] != "w" or rank < 2:
            return rep
        parent = parts[-2]
        gp = parts[-3] if len(parts) >= 3 else ""

        if gp == "moe":                        # routed expert stacks
            if parent == "router":
                return rep
            if axes.ep and rank >= 3 and shape[-3] % tps == 0:
                return _shard_dim(rank, -3, axes.ep)
            if axes.mtp:
                dim = -1 if parent in ("wi", "wg") else -2
                if ok(shape, dim, axes.mtp):
                    return _shard_dim(rank, dim, axes.mtp)
            return rep
        if parent == "embed":
            if axes.tv and shape[0] == cfg.vocab:
                return _shard_dim(rank, 0, axes.tv)
            return rep
        if parent == "head":
            if axes.tv and shape[-1] == cfg.vocab:
                return _shard_dim(rank, -1, axes.tv)
            return rep
        if parent in ("img_proj", "router"):
            return rep

        # attention projections shard only when heads divide (megatron);
        # ssm-family layers reuse the wk/wv/wo names for non-attention
        # projections and rg.* is the recurrent block — those follow the
        # plain tensor-parallel axis.
        is_attn = (parent in _ATTN_CORE and cfg.family != "ssm"
                   and gp != "rg")
        gate = axes.th if is_attn else axes.tp
        if not gate:
            return rep
        if parent in _ROW and ok(shape, -2, gate):
            return _shard_dim(rank, -2, gate)
        if parent in _COL and ok(shape, -1, gate):
            return _shard_dim(rank, -1, gate)
        return rep

    return fn


def _spec_shard_axes(spec: P) -> Tuple[Optional[int], Axes]:
    """First sharded dim of a weight spec -> (dim, mesh axes); (None, ())
    when fully replicated. Projection rules shard at most one dim."""
    for d, e in enumerate(tuple(spec)):
        if e is not None:
            return d, (e if isinstance(e, tuple) else (e,))
    return None, ()


def _axes_size(mesh, ax: Axes) -> int:
    sizes = _axis_sizes(mesh)
    return int(np.prod([sizes[a] for a in ax])) if ax else 1


def projection_shard_fn(cfg: ModelConfig, axes: MeshAxes):
    """Returns ``fn(name, w_shape) -> (shard_dim, shard_count)`` — the
    tensor-parallel split of one projection weight under ``axes``, in the
    form ``runtime.packing.pack_linear(shard_dim=, shard_count=)`` takes.
    ``name`` is the '/'-joined path of the weight leaf (ending "/w"), so
    the packed layout always follows the same megatron rule the fake-quant
    param tree would shard under."""
    fn = param_spec_fn(cfg, axes)

    def info(name: str, shape: Tuple[int, ...]):
        if not axes.enabled:
            return None, 1
        d, ax = _spec_shard_axes(fn(name, shape))
        if d is None:
            return None, 1
        return d, _axes_size(axes.mesh, ax)

    return info


def packed_specs(cfg: ModelConfig, params, axes: MeshAxes):
    """PartitionSpec tree for a packed serving param tree
    (``runtime.session.QuantizedSession.params``).

    Every ``PackedLinear`` leaf expands to a spec node of the same pytree
    structure (codes/scale/s_a children carry PartitionSpecs; the static
    bit metadata stays aux data, outside the spec tree) built from the
    *original* projection's partition rule:

    * ``codes`` shard along the weight's tensor-parallel dim (every
      layout packs along the contraction dim and keeps the others). A leaf
      is only sharded when it was packed per-shard for this mesh degree
      (or its layout is byte-per-code / packed off the shard dim, where
      plain packing is already per-shard exact); anything else replicates
      rather than splitting a byte or a plane block mid-shard.
    * ``scale`` follows the out-dim: sharded for column-parallel layers
      (per-channel ``(out,)``) and expert-parallel stacks (``(E, 1, 1)``),
      replicated for row-parallel ones (their per-channel scale spans the
      unsharded out dim).
    * ``s_a`` replicates except per-expert ``(E,)`` banks under expert
      parallelism.

    Non-packed leaves (embed/head, norms, reference-mode fake-quant
    dicts) follow ``param_spec_fn`` unchanged.
    """
    import dataclasses as _dc

    from repro.runtime.packing import PackedLinear

    fn = param_spec_fn(cfg, axes)

    def one(path, leaf):
        name = _path_name(path)
        if not isinstance(leaf, PackedLinear):
            return fn(name, tuple(leaf.shape))
        rank = len(leaf.shape)
        d, ax = _spec_shard_axes(fn(name + "/w", leaf.shape))
        n = _axes_size(axes.mesh, ax) if ax else 1
        codes = _replicate(leaf.codes.ndim)
        scale = _replicate(leaf.scale.ndim)
        s_a = _replicate(leaf.s_a.ndim)
        if d is not None and n > 1:
            per_shard = leaf.shard_dim == d and leaf.shard_count == n
            # plain plane blocks span the whole contraction dim
            plain_ok = not (leaf.layout == "planes" and d == rank - 2)
            if per_shard or (plain_ok and leaf.codes.shape[d] % n == 0):
                codes = _shard_dim(leaf.codes.ndim, d, ax)
            if (leaf.scale.ndim == 1 and d == rank - 1
                    and leaf.scale.shape[0] % n == 0):
                scale = P(ax)                       # column-parallel (out,)
            elif (leaf.scale.ndim == rank and d == 0
                    and leaf.scale.shape[0] % n == 0):
                scale = _shard_dim(rank, 0, ax)     # expert stack (E, 1, 1)
            if leaf.s_a.ndim == 1 and d == 0 and leaf.s_a.shape[0] % n == 0:
                s_a = P(ax)                         # per-expert (E,) bank
        return _dc.replace(leaf, codes=codes, scale=scale, s_a=s_a)

    return jax.tree_util.tree_map_with_path(
        one, params, is_leaf=lambda x: isinstance(x, PackedLinear))


def _path_name(path) -> str:
    return "/".join(
        str(getattr(k, "key", getattr(k, "name", getattr(k, "idx", k))))
        for k in path)


def param_specs(cfg: ModelConfig, params, axes: MeshAxes):
    """PartitionSpec tree mirroring ``params`` (arrays or ShapeDtypeStructs)."""
    fn = param_spec_fn(cfg, axes)
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: fn(_path_name(path), tuple(leaf.shape)), params)


def zero_sharded_specs(cfg: ModelConfig, params, axes: MeshAxes):
    """ZeRO-style optimizer-state specs: the base param spec widened by the
    data axes on the largest still-replicated dim that divides ``dp_size``
    (gradients/optimizer moments never need to be fully replicated)."""
    base = param_specs(cfg, params, axes)

    def widen(leaf, spec):
        if not axes.dp:
            return spec
        shape = tuple(leaf.shape)
        entries = list(spec) + [None] * (len(shape) - len(tuple(spec)))
        best = -1
        for i, (dim, e) in enumerate(zip(shape, entries)):
            if e is None and dim > 1 and dim % axes.dp_size == 0:
                if best < 0 or dim > shape[best]:
                    best = i
        if best >= 0:
            entries[best] = axes.dp
        return P(*entries)

    return jax.tree.map(widen, params, base)


def batch_specs(cfg: ModelConfig, batch, axes: MeshAxes):
    """Input specs: leading (batch) dim over the data axes when it divides;
    batch-1 cells (long-context decode) replicate."""
    def one(leaf):
        shape = tuple(leaf.shape)
        rank = len(shape)
        if (rank and axes.dp and shape[0] > 1
                and shape[0] % axes.dp_size == 0):
            return P(*((axes.dp,) + (None,) * (rank - 1)))
        return _replicate(rank)

    return jax.tree.map(one, batch)


def decode_state_specs(cfg: ModelConfig, state, axes: MeshAxes):
    """Decode-state (KV cache / recurrent state) specs: shard the batch dim
    — the continuous-batching engine's *slot* axis — over data axes. Body
    segments carry a leading (repeats,) stack dim, so their slot dim is
    index 1. Rank-(2+b) leaves cover the per-slot bookkeeping the engine
    adds (per-slot KVCache position rows (slots, cap), rank-2 recurrent
    hidden states); shared position vectors (cap,) and body-stacked shared
    positions (repeats, cap) stay below the rank gate and replicate.

    Int8 KV caches (``runtime.kv_cache.QuantKVCache``) need no special
    casing: their code tensors (slots, cap, KV, hd) and per-head scale
    tensors (slots, cap, KV) clear the same rank gate and shard on the
    slot dim, and the quantized runtime's flat per-site state ("sites"
    segment, no stack dim) takes the b = 0 branch."""
    def one(path, leaf):
        shape = tuple(leaf.shape)
        rank = len(shape)
        body = bool(path) and str(getattr(path[0], "key", "")) == "body"
        b = 1 if body else 0
        entries = [None] * rank
        if (rank >= 2 + b and axes.dp and shape[b] > 1
                and shape[b] % axes.dp_size == 0):
            entries[b] = axes.dp
        return P(*entries)

    return jax.tree_util.tree_map_with_path(one, state)


def named(mesh, spec_tree):
    """PartitionSpec tree -> NamedSharding tree on ``mesh``."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))
