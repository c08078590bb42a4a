"""Per-layer kernel dispatch for packed mixed-precision matmuls.

A ``PackedLinear`` carries its searched bit-widths as static metadata, so
every call site resolves — at trace time — which execution route serves it:

* ``pallas-w4``   — int4 weights in the ``nib4`` layout feed
  ``kernels.quant_matmul.quant_matmul_w4`` directly: the packed bytes are
  the kernel operand and nibbles unpack in the VMEM prologue (HBM never
  sees unpacked codes).
* ``pallas-planes`` — 3-, 5-, 6- and 7-bit weights in the ``planes``
  layout feed ``kernels.quant_matmul.quant_matmul_planes`` directly: the
  bit-plane bytes unpack in its VMEM prologue, as nib4 bytes do in the w4
  kernel.
* ``pallas-int8`` — any searched width ≤ 8 lands on a subset of the int8
  grid: codes unpack via XLA, activations quantize on the fly, and the
  matmul runs int8 x int8 -> int32 on the MXU
  (``kernels.quant_matmul.quant_matmul``). It serves 8-bit and 2-bit
  weights, and packed layouts the kernels above cannot tile.
* ``dequant-fp``  — exact fallback for everything the kernels can't tile
  (stacked MoE expert einsums, row-parallel ``(N,K)`` weight orientation,
  per-channel scales, odd contraction dims): dequantize the codes and run
  the same fp einsum as the fake-quant training graph. This route is
  *bit-exact* with that graph — it is the default off-TPU and what the
  serve smoke's token-identity gate runs on.

The Pallas routes are int32-exact per the kernel contract but not bitwise
equal to an fp einsum, so ``resolve`` only picks them on a TPU backend;
``force_impl`` overrides for interpret-mode equivalence tests.

Tensor parallelism (``axes_scope``): column-parallel layers need nothing —
codes, scale and the matmul all split on the output dim, every channel's
full-K contraction stays on one shard, and the result is bitwise equal to
the single-device einsum. Row-parallel layers (``...k,kn->...n`` with K
sharded, or the transposed ``...e,ed->...d`` orientation) are where the
megatron eqn splits the *contraction*:

    y = sum_s  x_s @ dequant(codes_s)        (s = shard)

each shard dequantizes its K-slab and computes a partial product, and the
cross-shard partial-sum reduce happens in fp. That split would be
order-independent — hence still exact — if the per-shard partial were
accumulated in integers (the int8/int4 MXU kernel routes: int32 partials,
fp only at the final scale). But a Pallas kernel cannot be partitioned
automatically, and the kernel routes have no shard_map plan yet, so a
forward bound to a multi-device mesh resolves every routed op to its fp
route (``_partitioned``). The fp route cannot use the split and stay
bitwise: fp MACs
reassociate under the split (measured: ~5e-5 per matmul, which the next
layer's quantization grid amplifies into full code-step jumps). So in the
``dequant-fp`` route each shard still dequantizes only its own slab, but
the slabs (and the activation) are then constrained replicated — SPMD
all-gathers them and the full-K einsum runs unsplit, reproducing the
single-device op chain bit-for-bit. Packed HBM storage stays sharded
either way; only the fp route's wire traffic pays for its exactness.
"""
from __future__ import annotations

import contextlib
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.core.quantizer import fake_quant, lsq_grad_scale_factor
from repro.kernels.quant_matmul import planes_tiled
from repro.models.quant_layers import row_einsum
from repro.runtime.packing import PackedLinear

Array = jax.Array

_AXES: List = [None]
_METRICS: List = [None]


# ---------------------------------------------------------------------------
# route table — one registry + one force mechanism for every routed op
# ---------------------------------------------------------------------------
class RouteTable:
    """Per-op route registry with one forcing mechanism.

    Each routed *op* (packed matmuls, int8 decode attention, the engine's
    KV layout) registers its legal route names here; ``force_route(op,
    name)`` pins one for a scope (the single seam behind the legacy
    ``force_impl`` / ``force_decode_attn`` context managers), ``validate``
    is what CLI flags (``serve --decode-attn`` / ``--kv-layout``) and
    engine config checks call, and ``resolve``/``resolve_decode_attn``
    consult the forced entry first. Forcing is a stack (scopes nest), and
    ``None`` restores auto-resolution.
    """

    def __init__(self, ops: Dict[str, tuple]):
        self.ops = {op: tuple(routes) for op, routes in ops.items()}
        self._forced: Dict[str, List[Optional[str]]] = {
            op: [None] for op in self.ops}

    def routes(self, op: str) -> tuple:
        if op not in self.ops:
            raise ValueError(f"unknown routed op {op!r}: {tuple(self.ops)}")
        return self.ops[op]

    def validate(self, op: str, name: str) -> str:
        routes = self.routes(op)
        if name not in routes:
            raise ValueError(f"unknown {op} route {name!r}: {routes}")
        return name

    def forced(self, op: str) -> Optional[str]:
        return self._forced[op][-1]

    @contextlib.contextmanager
    def force_route(self, op: str, name: Optional[str]):
        """Pin op ``op`` to route ``name`` for the scope (None = auto)."""
        if name is not None:
            self.validate(op, name)
        stack = self._forced[op]
        stack.append(name)
        try:
            yield
        finally:
            stack.pop()


ROUTES = RouteTable({
    "matmul": ("dequant-fp", "pallas-int8", "pallas-w4", "pallas-planes"),
    "decode_attn": ("fused", "fused-interpret", "dequant-fp"),
    "kv_layout": ("ring", "paged"),
    # the flash-attention forward of prefill and training: the Pallas
    # kernel, or the jnp online-softmax scan it falls back to
    "flash_fwd": ("pallas", "jnp-scan"),
    # how decode tokens are produced: plain target decode, or
    # self-speculative (the low-bit draft policy proposes, the searched
    # target policy verifies — launch/engine._spec_round)
    "spec": ("off", "self"),
    # which policy serves: one immutable policy per process, or a
    # pre-packed variant bank whose active member the admission-time ILP
    # re-solve hot-swaps between batches (launch/elastic.py)
    "elastic": ("off", "bank"),
})


def force_route(op: str, name: Optional[str]):
    """Module-level alias for ``ROUTES.force_route`` (the one force API)."""
    return ROUTES.force_route(op, name)


def force_impl(name: Optional[str]):
    """Pin every packed-matmul dispatch to ``name`` (tests; None restores
    auto). Legacy delegate for ``force_route("matmul", name)``."""
    return ROUTES.force_route("matmul", name)


@contextlib.contextmanager
def axes_scope(axes):
    """Bind the serving session's ``MeshAxes`` for the duration of one
    traced forward, so the dequant-fp route can pin its row-parallel
    gather (module docstring) without threading ``axes`` through every
    layer call site. No-op scope under ``NO_AXES``."""
    _AXES.append(axes if (axes is not None and axes.enabled) else None)
    try:
        yield
    finally:
        _AXES.pop()


@contextlib.contextmanager
def metrics_scope(registry):
    """Bind a ``repro.obs.metrics.MetricsRegistry`` for the duration of one
    traced forward, so dispatch can count which route each packed matmul
    (``dispatch.route.<impl>``) and the int8 decode attention
    (``dispatch.decode_attn.<route>``) resolved to. Counts are per *trace*
    (one compile), like ``act_reuse_scope`` hits — the jitted graph
    dispatches once, not per executed step. No-op scope under ``None``."""
    _METRICS.append(registry)
    try:
        yield
    finally:
        _METRICS.pop()


def _partitioned() -> bool:
    """True inside a forward bound to a multi-device mesh (``axes_scope``):
    the Pallas routes need a shard_map plan there, which does not exist
    yet, so every routed op takes its fp route."""
    axes = _AXES[-1]
    return axes is not None and axes.mesh.size > 1


def _count_route(family: str, route: str) -> None:
    reg = _METRICS[-1]
    if reg is not None:
        reg.counter(f"dispatch.{family}.{route}").inc()


def dominant_route(registry, family: str = "route") -> str:
    """Most-counted ``dispatch.<family>.*`` impl in a registry ("fp" when
    nothing was counted). Route counts are per trace; the engine uses this
    to attribute its measured phase latencies to the impl that actually
    serves the compiled graph (``obs.health.attribute_latency``)."""
    prefix = f"dispatch.{family}."
    best, best_count = "fp", 0.0
    for name in getattr(registry, "_metrics", {}):
        if name.startswith(prefix):
            v = registry.value(name)
            if v > best_count:
                best, best_count = name[len(prefix):], v
    return best


def _w_contracted_dims(eqn: str):
    """Indices of the weight dims the einsum contracts away."""
    try:
        lhs, out = eqn.split("->")
        xs, ws = lhs.split(",")
    except ValueError:
        return frozenset()
    return frozenset(i for i, c in enumerate(ws) if c in xs and c not in out)


# ---------------------------------------------------------------------------
# decode-attention routing (int8 KV cache)
# ---------------------------------------------------------------------------
# Decode attention over a ``QuantKVCache`` resolves one of three routes —
# the matmul registry's sibling for the serving hot path:
#
# * ``fused``           — the Pallas kernel attends directly on the int8
#   codes + f32 scales (kernels.quant_attention): decode-attention HBM
#   traffic is code-sized. TPU backends only.
# * ``fused-interpret`` — the same kernel program through the Pallas
#   interpreter: CI's proof that the fused route is greedy-token-identical
#   to the dequant reference without TPU hardware.
# * ``dequant-fp``      — dequantize the whole cache and run the fp masked
#   softmax (models.attention). Exact reference; default off-TPU.
#
# Like matmul routes, resolution happens at trace time; the engine also
# resolves once at build for its roofline accounting, so a force scope
# must wrap engine construction AND its first run.
DECODE_ATTN_ROUTES = ROUTES.routes("decode_attn")


def force_decode_attn(name: Optional[str]):
    """Pin the int8 decode-attention route (tests/CLI; None restores auto).
    Legacy delegate for ``force_route("decode_attn", name)``."""
    return ROUTES.force_route("decode_attn", name)


def resolve_decode_attn(backend: Optional[str] = None) -> str:
    """Route for decode attention over an int8 KV cache (see above)."""
    route = ROUTES.forced("decode_attn")
    if route is None:
        backend = backend or jax.default_backend()
        fused = backend == "tpu" and not _partitioned()
        route = "fused" if fused else "dequant-fp"
    _count_route("decode_attn", route)
    return route


def resolve_flash_fwd(seq_len: int, kv_block: int,
                      backend: Optional[str] = None) -> str:
    """Route for the flash-attention forward: the Pallas kernel on a TPU
    when the sequence tiles into ``kv_block`` blocks, else the jnp scan.
    Counted like the other routes, so a TPU prefill whose length does not
    tile shows up as ``dispatch.flash_fwd.jnp-scan``."""
    route = ROUTES.forced("flash_fwd")
    if route is None:
        backend = backend or jax.default_backend()
        tiles = seq_len % kv_block == 0
        pallas = backend == "tpu" and tiles and not _partitioned()
        route = "pallas" if pallas else "jnp-scan"
    _count_route("flash_fwd", route)
    return route


# ---------------------------------------------------------------------------
# activation-code reuse (one quantize per site for wq/wk/wv-style fans)
# ---------------------------------------------------------------------------
_SCOPE: List[Optional[dict]] = [None]


@contextlib.contextmanager
def act_reuse_scope():
    """Memoize quantized activations for the duration of one traced
    forward pass.

    Projections that consume the *same* hidden state with bit-identical
    quantization parameters — wq/wk/wv on a site's normed residual, an MoE
    stack's wi/wg on the gathered tokens — otherwise each quantize that
    activation again. Inside this scope, ``act_fake_quant``/``act_codes``
    cache by ``(input identity, PackedLinear.a_group)``: the session
    assigns matching ``a_group`` tags at pack time only to layers whose
    (a_bits, a_signed, trained bank scale values) are equal, so a cache
    hit returns the exact array the miss would have computed and token
    identity with the per-layer-quantizing reference graph is preserved.

    Yields a dict whose ``"hits"`` counts elided quantize ops. The count
    is per *trace* (one compile), not per executed step — it measures ops
    removed from the jitted graph (surfaced as
    ``EngineStats.act_quant_reused``).
    """
    scope = {"cache": {}, "hits": 0}
    _SCOPE.append(scope)
    try:
        yield scope
    finally:
        _SCOPE.pop()


def _reuse_lookup(x: Array, pl: PackedLinear, tag: str):
    """(cache_key, hit_or_None). The cached entry keeps a reference to the
    input array so an id() recycled by the allocator can never alias."""
    scope = _SCOPE[-1]
    if scope is None or not pl.a_group:
        return None, None
    key = (id(x), pl.a_group, tag)
    entry = scope["cache"].get(key)
    if entry is not None and entry[0] is x:
        scope["hits"] += 1
        return key, entry[1]
    return key, None


def _reuse_store(key, x: Array, value):
    if key is not None:
        _SCOPE[-1]["cache"][key] = (x, value)


# ---------------------------------------------------------------------------
# activation quantization (bit-exact with quant_layers._maybe_quant_a)
# ---------------------------------------------------------------------------
def _act_scale(x: Array, pl: PackedLinear) -> Array:
    """The bank scale aligned to the activation — trailing-ones broadcast
    for per-expert banks, exactly ``fake_quant_indexed``'s reshape."""
    s = pl.s_a
    if s.ndim:
        s = s.reshape(s.shape + (1,) * (x.ndim - s.ndim))
    return s


def act_fake_quant(x: Array, pl: PackedLinear, ctx) -> Array:
    """LSQ fake-quant of activations at the layer's searched a_bits, using
    the trained bank scale — the identical op chain (scale floor, LSQ grad
    wrapper, clip bounds, per-expert broadcast) as the training graph, for
    bitwise parity."""
    if not (ctx.enabled and ctx.quantize_acts):
        return x
    key, hit = _reuse_lookup(x, pl, "fake")
    if hit is not None:
        return hit
    qmin, qmax = pl.a_range
    g = lsq_grad_scale_factor(x.size, qmax)
    out = fake_quant(x, _act_scale(x, pl), qmin, qmax, grad_scale_factor=g)
    _reuse_store(key, x, out)
    return out


def act_codes(x: Array, pl: PackedLinear, ctx):
    """Integer activation codes + scale for the int8 kernel routes
    (per-tensor scale only — kernel-eligible layers are never stacked)."""
    key, hit = _reuse_lookup(x, pl, "codes")
    if hit is not None:
        return hit
    qmin, qmax = pl.a_range
    s = jnp.maximum(pl.s_a.reshape(()), 1e-9)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s), qmin, qmax)
    out = (q.astype(jnp.int8), s)
    _reuse_store(key, x, out)
    return out


# ---------------------------------------------------------------------------
# eqn analysis
# ---------------------------------------------------------------------------
def _kernel_form(eqn: str) -> bool:
    """True for ``...k,kn->...n`` einsums — weight is (K, N) with the
    contraction on the activation's last dim (the only orientation the
    Pallas kernels tile)."""
    try:
        lhs, out = eqn.split("->")
        xs, ws = lhs.split(",")
    except ValueError:
        return False
    return (len(ws) == 2 and xs[-1] == ws[0] and out[-1] == ws[1]
            and ws[1] not in xs)


# ---------------------------------------------------------------------------
# implementations
# ---------------------------------------------------------------------------
def _replicate(mesh, a: Array) -> Array:
    from jax.sharding import NamedSharding, PartitionSpec as P
    return jax.lax.with_sharding_constraint(
        a, NamedSharding(mesh, P(*([None] * a.ndim))))


def _impl_dequant_fp(eqn: str, x: Array, pl: PackedLinear, ctx) -> Array:
    with jax.named_scope("act_codes"):
        xq = act_fake_quant(x, pl, ctx).astype(ctx.compute_dtype)
    axes = _AXES[-1]
    if axes is not None and pl.shard_count > 1:
        # Gather the *packed* codes — the cheapest form on the wire, and
        # the only per-step tp traffic this route adds — then unpack,
        # dequant and contract replicated. Row-parallel weights REQUIRE
        # the gather so the fp full-K contraction does not split (module
        # docstring); the rest take it too because the sub-byte unpack is
        # reshape/slice-heavy and a replicated stream keeps the op chain
        # identical to the single-device graph op for op. HBM storage
        # between steps stays sharded regardless — this trades wire for
        # bitwise exactness, which is the fallback's contract; the MXU
        # kernel routes keep shard-local slabs and the int32-exact
        # partial-sum split.
        import dataclasses
        codes, scale = jax.lax.optimization_barrier(
            (_replicate(axes.mesh, pl.codes),
             _replicate(axes.mesh, pl.scale)))
        pl = dataclasses.replace(pl, codes=codes, scale=scale)
        if pl.shard_dim in _w_contracted_dims(eqn):
            xq = _replicate(axes.mesh, xq)
        # the barriers bracket the unpack chain so the SPMD partitioner
        # cannot re-fuse it across the gather boundary — left free, the
        # 0.4.37 CPU partitioner re-tiles the packed-stream reshapes and
        # produces wrong slabs (only when the chain stays internal to a
        # larger jit; any materialization hides it)
        with jax.named_scope("dequant"):
            w = jax.lax.optimization_barrier(pl.dequant(ctx.compute_dtype))
        return row_einsum(eqn, xq, w)
    with jax.named_scope("dequant"):
        w = pl.dequant(ctx.compute_dtype)
    return row_einsum(eqn, xq, w)


def _scalar_scale(pl: PackedLinear) -> Array:
    return pl.scale.reshape(-1)[0]


def _kernel_call(eqn, x, pl, ctx, matmul):
    with jax.named_scope("act_codes"):
        xq, s_x = act_codes(x, pl, ctx)
    m2 = xq.reshape(-1, xq.shape[-1])
    with jax.named_scope("kernel"):
        out = matmul(m2, s_x)
    return out.reshape(x.shape[:-1] + (out.shape[-1],)).astype(
        ctx.compute_dtype)


def _impl_pallas_int8(eqn: str, x: Array, pl: PackedLinear, ctx) -> Array:
    from repro.kernels import ops
    w_codes = pl.unpack()
    return _kernel_call(
        eqn, x, pl, ctx,
        lambda m2, s_x: ops.quant_matmul(m2, w_codes, s_x,
                                         _scalar_scale(pl)))


def _impl_pallas_w4(eqn: str, x: Array, pl: PackedLinear, ctx) -> Array:
    from repro.kernels import ops
    return _kernel_call(
        eqn, x, pl, ctx,
        lambda m2, s_x: ops.quant_matmul_w4(m2, pl.codes, s_x,
                                            _scalar_scale(pl),
                                            k=pl.shape[-2]))


def _impl_pallas_planes(eqn: str, x: Array, pl: PackedLinear, ctx) -> Array:
    from repro.kernels import ops
    return _kernel_call(
        eqn, x, pl, ctx,
        lambda m2, s_x: ops.quant_matmul_planes(m2, pl.codes, s_x,
                                                _scalar_scale(pl),
                                                bits=pl.w_bits))


REGISTRY: Dict[str, Callable] = {
    "dequant-fp": _impl_dequant_fp,
    "pallas-int8": _impl_pallas_int8,
    "pallas-w4": _impl_pallas_w4,
    "pallas-planes": _impl_pallas_planes,
}


# ---------------------------------------------------------------------------
# resolution
# ---------------------------------------------------------------------------
def kernel_eligible(eqn: str, pl: PackedLinear) -> Optional[str]:
    """The Pallas route this (eqn, layer) pair could take, else None."""
    if len(pl.shape) != 2 or not _kernel_form(eqn):
        return None
    if pl.per_channel:  # kernel epilogue takes a per-tensor scale (for now)
        return None
    if not pl.a_signed and pl.a_bits > 7:
        return None  # unsigned 8-bit grid (qmax 255) overflows int8 codes
    if (pl.layout == "nib4" and pl.shape[-2] % 2 == 0
            and not pl.sharded_layout()):
        # the w4 kernel consumes the PLAIN nib4 byte stream; a per-shard
        # re-broken layout (odd per-shard rows) must go through unpack
        return "pallas-w4"
    if (pl.layout == "planes" and not pl.sharded_layout()
            and planes_tiled(*pl.shape)):
        # the planes kernel reads the plane bytes as stored: they must
        # tile as they are, else the codes unpack in XLA
        return "pallas-planes"
    if pl.w_bits <= 8:
        return "pallas-int8"
    return None


def resolve(eqn: str, pl: PackedLinear, backend: Optional[str] = None) -> str:
    """Pick the execution route for one packed matmul (see module doc)."""
    forced = ROUTES.forced("matmul")
    if forced is not None:
        return forced
    backend = backend or jax.default_backend()
    if backend != "tpu" or _partitioned():
        return "dequant-fp"
    return kernel_eligible(eqn, pl) or "dequant-fp"


def packed_qeinsum(eqn: str, x: Array, pl: PackedLinear, ctx,
                   impl: Optional[str] = None) -> Array:
    """Quantized einsum over a packed weight — the serving-time counterpart
    of ``quant_layers.qeinsum`` (which routes here when it sees a
    ``PackedLinear`` instead of a fake-quant param dict)."""
    impl = impl or resolve(eqn, pl)
    _count_route("route", impl)
    return REGISTRY[impl](eqn, x, pl, ctx)
