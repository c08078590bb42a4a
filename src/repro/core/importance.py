"""Joint one-shot importance-indicator training (paper §3.4).

At every step the atomic update runs `n` forward/backward passes — the whole
network uniformly at bit option k — plus ONE pass at a random per-layer bit
assignment (the "communication" pass, one-shot-NAS style). The n+1 gradients
are aggregated and applied in a single optimizer update, so all
`M = 2 * L * n` indicators are learned in one QAT run instead of M runs.

Paper finding (§3.4 last paragraph): freezing the backbone weights and
training *only* the indicators yields near-identical indicators; both modes
are exposed (``freeze_backbone``).

``extract_indicators`` then reads the learned banks out of the param tree in
QLayer order, producing exactly what ``repro.core.search.search_policy``
(Eq. 3) consumes.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.configs.base import ModelConfig
from repro.core.qspec import QLayer
from repro.dist.axes import NO_AXES, MeshAxes
from repro.models import lm
from repro.models.quant_layers import QuantContext

Indicators = Dict[str, Dict[str, np.ndarray]]


def importance_optimizer(lr: float = 0.01, momentum: float = 0.9,
                         freeze_backbone: bool = True,
                         clip_norm: Optional[float] = 1.0) -> optim.Optimizer:
    """Paper §4.1: SGD, lr=0.01. With freeze_backbone only the scale banks
    (the indicators) receive updates."""
    base = optim.sgd(lr, momentum=momentum, clip_norm=clip_norm)
    if freeze_backbone:
        return optim.masked(base, optim.indicator_only_mask)
    return base


def make_importance_step(cfg: ModelConfig, ctx: QuantContext,
                         optimizer: optim.Optimizer,
                         axes: MeshAxes = NO_AXES, *,
                         include_random_pass: bool = True,
                         remat: bool = True) -> Callable:
    """Returns jit-able step(params, opt_state, batch, rng) ->
    (params, opt_state, metrics). One call = the paper's atomic operation.

    Each pass differentiates only the leaves ``optimizer.trainable`` keeps
    (the indicator banks under ``importance_optimizer(freeze_backbone=
    True)``); the others get zero gradients. The optimizer would zero them
    anyway, and never forming them keeps the n + 1 weight-sized gradient
    trees out of device memory."""
    n = cfg.n_bits
    mask = optimizer.trainable

    def trainable(params):
        if mask is None:
            return params
        return jax.tree_util.tree_map_with_path(
            lambda path, x: x if mask(path, x) else None, params)

    def with_frozen(train, params):
        return jax.tree.map(lambda t, p: p if t is None else t, train, params,
                            is_leaf=lambda x: x is None)

    def loss_of(train, params, batch, bits):
        return lm.loss_fn(with_frozen(train, params), cfg, batch, bits, ctx,
                          axes, remat=remat)[0]

    def step(params, opt_state, batch, rng):
        train = trainable(params)

        def uniform_pass(grads_sum, k):
            l, g = jax.value_and_grad(loss_of)(train, params, batch,
                                               lm.bits_uniform(cfg, k))
            return jax.tree.map(jnp.add, grads_sum, g), l

        # the n uniform-bit passes run as one scanned body: one pass's
        # activations are live at a time, and the step compiles one pass
        # instead of n
        grads_sum, losses = jax.lax.scan(
            uniform_pass, jax.tree.map(jnp.zeros_like, train), jnp.arange(n))
        if include_random_pass:                    # communication pass
            l_r, g = jax.value_and_grad(loss_of)(
                train, params, batch, lm.bits_random(cfg, rng))
            grads_sum = jax.tree.map(jnp.add, grads_sum, g)
        else:
            l_r = jnp.zeros(())
        grads_sum = jax.tree.map(
            lambda g, p: jnp.zeros_like(p) if g is None else g, grads_sum,
            params, is_leaf=lambda x: x is None)
        # aggregate the n+1 gradients into one atomic update (§3.4):
        # backbone weights receive signal from every pass -> average over
        # all of them. A bank ENTRY is selected by its own uniform pass
        # plus at most the random pass, so the banks are normalized by
        # that upper bound (2) instead — a deliberately conservative
        # fixed constant, not a per-entry average: a flat 1/(n+1) would
        # dilute the indicator gradients ~(n+1)/2-fold relative to their
        # lr, while the exact expectation (1 + 1/n) over-amplifies the
        # entries the random pass did not actually select
        n_passes = n + (1 if include_random_pass else 0)
        bank_passes = 2 if include_random_pass else 1
        grads = jax.tree_util.tree_map_with_path(
            lambda path, g: g / (bank_passes
                                 if optim.indicator_only_mask(path, g)
                                 else n_passes),
            grads_sum)

        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optim.apply_updates(params, updates)
        metrics = {"loss_uniform": losses, "loss_random": l_r}
        return params, opt_state, metrics

    return step


def train_importance(params, cfg: ModelConfig, ctx: QuantContext,
                     batches, *, lr: float = 0.01,
                     freeze_backbone: bool = True,
                     axes: MeshAxes = NO_AXES, remat: bool = False,
                     jit: bool = True):
    """Convenience loop: run the joint scheme over `batches` (an iterable).
    Returns (params, history)."""
    opt = importance_optimizer(lr, freeze_backbone=freeze_backbone)
    step = make_importance_step(cfg, ctx, opt, axes, remat=remat)
    if jit:
        step = jax.jit(step)
    opt_state = opt.init(params)
    rng = jax.random.PRNGKey(1234)
    history = []
    for batch in batches:
        rng, sub = jax.random.split(rng)
        params, opt_state, m = step(params, opt_state, batch, sub)
        history.append(jax.device_get(m))
    return params, history


# ---------------------------------------------------------------------------
# indicator extraction
# ---------------------------------------------------------------------------
def _qparam_node(params, segment: str, path):
    seg, idx = segment.split(".")
    node = params[seg][idx]
    for k in path:
        node = node[k]
    return node


def extract_indicators(params, cfg: ModelConfig,
                       qlayers: Optional[Sequence[QLayer]] = None) -> Indicators:
    """Read the learned (n_bits,) banks per QLayer. Body banks are stacked
    (repeats, ..., n); MoE expert stacks are averaged over the expert dim —
    one QLayer spans the whole stacked tensor."""
    qlayers = qlayers if qlayers is not None else lm.enumerate_qlayers(cfg)
    out: Indicators = {}
    for q in qlayers:
        node = _qparam_node(params, q.segment, q.path)
        s_w = np.asarray(jax.device_get(node["s_w"]), np.float64)
        s_a = np.asarray(jax.device_get(node["s_a"]), np.float64)
        if q.segment.startswith("body."):
            s_w, s_a = s_w[q.unit], s_a[q.unit]
        while s_w.ndim > 1:            # MoE expert dim
            s_w = s_w.mean(axis=0)
        while s_a.ndim > 1:
            s_a = s_a.mean(axis=0)
        out[q.name] = {"w": np.abs(s_w), "a": np.abs(s_a)}
    return out


def indicators_summary(ind: Indicators, bits) -> str:
    lines = ["layer".ljust(28) + "  " + "  ".join(f"w@{b}b" for b in bits)
             + "  |  " + "  ".join(f"a@{b}b" for b in bits)]
    for name, d in ind.items():
        lines.append(name.ljust(28) + "  "
                     + "  ".join(f"{v:.4f}" for v in d["w"])
                     + "  |  " + "  ".join(f"{v:.4f}" for v in d["a"]))
    return "\n".join(lines)
