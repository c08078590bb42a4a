"""Mixture-of-Experts FFN: capacity-bounded dispatch for training, dropless
dispatch for serving.

Training (``moe_ffn``, TPU-idiomatic, DESIGN.md §3): token-choice top-k
routing with *per-expert top-C token selection* for capacity enforcement —
no sort, no giant one-hot dispatch tensors. Each expert gathers its C
highest-gate tokens into an (E, C, D) buffer (E shards over the model axis
for fine-grained MoE, C over the data axes), runs dense 128-aligned
matmuls, and scatter-adds results back. Overflow tokens are dropped exactly
like capacity-factor dispatch in Mesh-TF/MaxText. The capacity applies to
training only.

Serving (``moe_ffn(..., dropless=True)``: prefill, decode, verify and
append) drops no token: the (token, expert) pairs are sorted by expert, each
expert's rows run through its slab of the stacked weights by a grouped
matmul (``quant_layers.qgrouped``; the ``pallas-grouped`` kernel route on a
TPU), and a weighted scatter-add combines them. ``load_scope`` collects the
tokens each expert took, per MoE layer, for the serving session's
expert-load counter.

Routing (router logits, softmax, top-k) stays in f32 and is NOT quantized
(DESIGN.md §5 — precision-critical and tiny); ``MoEConfig.norm_topk``
says whether the top-k weights are renormalised. Expert matmuls are
QLayers: one (E, ...) stacked tensor per projection with a shared
per-tensor indicator bank, activated-MAC BitOps accounting.
"""
from __future__ import annotations

import contextlib
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.dist.axes import MeshAxes
from repro.models.common import activation, dense_init
from repro.models.quant_layers import (QuantContext, qdense_init, qeinsum,
                                      qgrouped)

Array = jax.Array


# Perf switch (EXPERIMENTS.md §Perf): True = shard-local routing; False =
# the paper-faithful-baseline global top-C dispatch (G=1).
GROUP_LOCAL_DISPATCH = True


_LOADS: List[Optional[list]] = [None]


@contextlib.contextmanager
def load_scope():
    """Collect, for one traced forward, each dropless MoE layer's tokens per
    expert ((E,) int32, in layer order) into the yielded list."""
    loads: list = []
    _LOADS.append(loads)
    try:
        yield loads
    finally:
        _LOADS.pop()


def route(xf: Array, w_router: Array, moe: MoEConfig):
    """Router in f32 (at 'highest' precision, so a TPU does not round its
    operands to bfloat16): (probs (..., E), top-k weights, top-k experts).
    The weights are the softmax probabilities of the chosen experts,
    renormalised to sum to one where ``moe.norm_topk``."""
    logits = jnp.einsum("...d,de->...e", xf.astype(jnp.float32),
                        w_router.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, moe.top_k)
    if moe.norm_topk:
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    return probs, top_w, top_i


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def capacity(n_tokens: int, moe: MoEConfig, factor: float = 1.25,
             align: int = 128) -> int:
    c = int(n_tokens * moe.top_k / moe.n_experts * factor)
    c = min(round_up(c, align), n_tokens)          # top_k needs C <= n_tokens
    return max(min(align, n_tokens), c)


def moe_init(rng, d_model: int, moe: MoEConfig, bits, gated: bool,
             *, stacked=()):
    ks = jax.random.split(rng, 8)
    E, Fe = moe.n_experts, moe.d_ff
    p = {
        "router": {"w": dense_init(ks[0], d_model, E, stacked=stacked)},
        "wi": qdense_init(ks[1], d_model, Fe, bits, stacked=stacked + (E,)),
        "wo": qdense_init(ks[2], Fe, d_model, bits, stacked=stacked + (E,)),
    }
    if gated:
        p["wg"] = qdense_init(ks[3], d_model, Fe, bits, stacked=stacked + (E,))
    if moe.n_shared:
        Fs = moe.n_shared * Fe
        p["shared_wi"] = qdense_init(ks[4], d_model, Fs, bits, stacked=stacked)
        p["shared_wo"] = qdense_init(ks[5], Fs, d_model, bits, stacked=stacked)
        if gated:
            p["shared_wg"] = qdense_init(ks[6], d_model, Fs, bits, stacked=stacked)
    return p


def _shared(xf: Array, p, moe: MoEConfig, b, ctx: QuantContext, act_fn,
            gated: bool) -> Array:
    """The always-on shared experts, one gated MLP of width n_shared * d_ff."""
    hs = qeinsum("td,df->tf", xf, p["shared_wi"], b("shared_wi"), ctx)
    if gated:
        gs = qeinsum("td,df->tf", xf, p["shared_wg"], b("shared_wg"), ctx)
        hs = act_fn(gs) * hs
    else:
        hs = act_fn(hs)
    return qeinsum("tf,fd->td", hs, p["shared_wo"], b("shared_wo"), ctx)


def _moe_dropless(x: Array, p, moe: MoEConfig, bits: Optional[Dict],
                  ctx: QuantContext, act: str, gated: bool):
    """Serving dispatch: every (token, expert) pair the router picks is
    computed. Returns (out (B, S, D), zero aux loss)."""
    B, S, D = x.shape
    T = B * S
    E, K = moe.n_experts, moe.top_k
    act_fn = activation(act)
    xf = x.reshape(T, D)

    def b(name):
        return None if bits is None else bits[name]

    with jax.named_scope("moe_route"):
        _, top_w, top_i = route(xf, p["router"]["w"], moe)
    with jax.named_scope("moe_dispatch"):
        flat = top_i.reshape(T * K)
        order = jnp.argsort(flat, stable=True)      # pairs sorted by expert
        row_e = flat[order]
        tok = order // K
        sizes = jnp.zeros((E,), jnp.int32).at[flat].add(1)
        xs = jnp.take(xf, tok, axis=0)              # (T*K, D)
    with jax.named_scope("moe_experts"):
        h = qgrouped(xs, p["wi"], b("wi"), sizes, row_e, ctx)
        if gated:
            g = qgrouped(xs, p["wg"], b("wg"), sizes, row_e, ctx)
            h = act_fn(g) * h
        else:
            h = act_fn(h)
        y = qgrouped(h, p["wo"], b("wo"), sizes, row_e, ctx)
    with jax.named_scope("moe_combine"):
        w_rows = top_w.reshape(T * K)[order].astype(y.dtype)
        out = jnp.zeros((T, D), y.dtype).at[tok].add(y * w_rows[:, None])
    if moe.n_shared:
        with jax.named_scope("shared_expert"):
            out = out + _shared(xf, p, moe, b, ctx, act_fn, gated)
    if _LOADS[-1] is not None:
        _LOADS[-1].append(sizes)
    return out.reshape(B, S, D), jnp.zeros((), jnp.float32)


def moe_ffn(x: Array, p, moe: MoEConfig, bits: Optional[Dict], ctx: QuantContext,
            act: str, gated: bool, axes: MeshAxes,
            capacity_factor: float = 1.25, dropless: bool = False):
    """x: (B, S, D) -> (out (B, S, D), aux_loss scalar f32).

    ``dropless`` (serving) computes every routed pair (module docstring);
    otherwise the capacity dispatch below runs (training).

    Dispatch is GROUP-LOCAL: tokens are split into `dp_size` groups aligned
    with the data shards and each group routes to per-group expert capacity
    C/G. Routing then never crosses data shards — the baseline (global
    top-C) all-gathered the full (T, D) token stream per MoE layer, the
    single largest collective in the roofline table (EXPERIMENTS.md §Perf).
    Per-shard capacity is the standard Mesh-TF/MaxText semantics.
    """
    if dropless:
        return _moe_dropless(x, p, moe, bits, ctx, act, gated)
    B, S, D = x.shape
    T = B * S
    E, K = moe.n_experts, moe.top_k
    act_fn = activation(act)
    # Group-local routing pays when experts are replicated/ffn-sharded
    # (mixtral: -82% wire bytes). Under expert parallelism the tokens must
    # cross to the expert shards anyway and per-group routing only
    # fragments that transfer (deepseek: +2.2x wire, measured) — keep the
    # global dispatch there. EXPERIMENTS.md §Perf iteration 4.
    G = axes.dp_size if (GROUP_LOCAL_DISPATCH and axes.enabled
                         and not axes.ep
                         and T % max(axes.dp_size, 1) == 0) else 1
    Tg = T // G
    # sharding a size-1 group axis would make SPMD pad the tensor dp_size-x
    # (measured: 4x step blowup) — target the token axis when ungrouped
    gdim, tdim = ("dp", None) if G > 1 else (None, "dp")
    xf = x.reshape(G, Tg, D)
    xf = axes.shard(xf, gdim, tdim, None)

    # ---- routing (f32, unquantized), per group -----------------------------
    probs, top_w, top_i = route(xf, p["router"]["w"], moe)    # (G, Tg, .)
    gates = jnp.sum(jax.nn.one_hot(top_i, E, dtype=jnp.float32)
                    * top_w[..., None], axis=2)                 # (G, Tg, E)

    # load-balance aux loss (Switch-style), averaged over groups
    frac_tokens = jnp.mean((gates > 0).astype(jnp.float32), axis=1)
    frac_probs = jnp.mean(probs, axis=1)
    aux = E * jnp.mean(jnp.sum(frac_tokens * frac_probs, axis=-1))

    # ---- capacity-bounded dispatch: per-(group, expert) top-C tokens ------
    C = capacity(Tg, moe, capacity_factor)
    gv, gi = jax.lax.top_k(gates.transpose(0, 2, 1), C)         # (G, E, C)
    keep = (gv > 0.0).astype(jnp.float32)
    bidx = jnp.arange(G)[:, None, None]
    if G == 1:
        # flat gather — the batched advanced-indexing form lowers to a
        # far worse scatter/gather under SPMD (measured: 4x bytes)
        xg = jnp.take(xf[0], gi[0].reshape(-1), axis=0).reshape(1, E, C, D)
    else:
        xg = xf[bidx, gi]                                       # (G, E, C, D)
    # fold groups into the capacity axis: C' = G*C, group-major, so the
    # dp sharding of C' lands each group on its own data shard.
    xg = xg.transpose(1, 0, 2, 3).reshape(E, G * C, D)
    xg = axes.shard(xg, "ep", "dp", None)

    # ---- expert matmuls (quantized) ---------------------------------------
    def b(name):
        return None if bits is None else bits[name]
    h = qeinsum("ecd,edf->ecf", xg, p["wi"], b("wi"), ctx)
    if gated:
        g = qeinsum("ecd,edf->ecf", xg, p["wg"], b("wg"), ctx)
        h = act_fn(g) * h
    else:
        h = act_fn(h)
    h = axes.shard(h, "ep", "dp", "mtp")
    y = qeinsum("ecf,efd->ecd", h, p["wo"], b("wo"), ctx)       # (E, G*C, D)
    y = y.reshape(E, G, C, D).transpose(1, 0, 2, 3)             # (G, E, C, D)
    y = y * (gv * keep)[..., None].astype(y.dtype)

    # ---- combine: scatter-add back to tokens, per group --------------------
    if G == 1:
        out = jnp.zeros((Tg, D), y.dtype).at[gi.reshape(-1)].add(
            y.reshape(E * C, D), mode="drop")[None]
    else:
        out = jnp.zeros((G, Tg, D), y.dtype).at[bidx, gi].add(y, mode="drop")
    out = axes.shard(out, gdim, tdim, None)
    out = out.reshape(T, D)
    xf = xf.reshape(T, D)

    # ---- shared experts (always-on) ---------------------------------------
    if moe.n_shared:
        out = out + _shared(xf, p, moe, b, ctx, act_fn, gated)

    return out.reshape(B, S, D), aux.astype(jnp.float32)


def moe_qlayer_defs(d_model: int, moe: MoEConfig, gated: bool):
    """(path, in, out, n_mats, macs_per_token, params, kind) tuples."""
    E, K, Fe = moe.n_experts, moe.top_k, moe.d_ff
    defs = [
        (("wi",), d_model, Fe, E, K * d_model * Fe, E * d_model * Fe, "moe"),
        (("wo",), Fe, d_model, E, K * Fe * d_model, E * Fe * d_model, "moe"),
    ]
    if gated:
        defs.append((("wg",), d_model, Fe, E, K * d_model * Fe,
                     E * d_model * Fe, "moe"))
    if moe.n_shared:
        Fs = moe.n_shared * Fe
        defs += [
            (("shared_wi",), d_model, Fs, 1, d_model * Fs, d_model * Fs, "moe"),
            (("shared_wo",), Fs, d_model, 1, Fs * d_model, Fs * d_model, "moe"),
        ]
        if gated:
            defs.append((("shared_wg",), d_model, Fs, 1, d_model * Fs,
                         d_model * Fs, "moe"))
    return defs
