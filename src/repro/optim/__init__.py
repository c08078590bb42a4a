"""Minimal optax-style optimizers (optax is not available offline).

An ``Optimizer`` is (init, update); ``update`` maps (grads, state, params)
-> (updates, state) where updates are ADDED to params. Provided:

  * ``sgd`` (momentum), ``adamw`` (decoupled weight decay, f32 master)
  * ``cosine_warmup`` schedule
  * ``clip_by_global_norm`` gradient transform
  * ``masked`` — freeze subsets of the tree (paper §3.4's freeze-backbone
    indicator training; also embedding-frozen finetune ablations)
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array
Schedule = Callable[[Array], Array]      # step -> lr


class Optimizer(NamedTuple):
    init: Callable[[Any], Any]
    update: Callable[[Any, Any, Any], Tuple[Any, Any]]
    # (path, leaf) -> bool for the leaves this optimizer moves; None = all.
    # A step may skip forming gradients for the others (``masked`` sets it)
    trainable: Optional[Callable[[Any, Any], bool]] = None


# ---------------------------------------------------------------------------
# schedules
# ---------------------------------------------------------------------------
def constant(lr: float) -> Schedule:
    return lambda step: jnp.asarray(lr, jnp.float32)


def cosine_warmup(peak_lr: float, warmup_steps: int, total_steps: int,
                  end_frac: float = 0.0) -> Schedule:
    def sched(step):
        step = jnp.asarray(step, jnp.float32)
        warm = step / jnp.maximum(warmup_steps, 1)
        prog = (step - warmup_steps) / jnp.maximum(total_steps - warmup_steps, 1)
        prog = jnp.clip(prog, 0.0, 1.0)
        cos = end_frac + (1 - end_frac) * 0.5 * (1 + jnp.cos(jnp.pi * prog))
        return peak_lr * jnp.where(step < warmup_steps, warm, cos)
    return sched


# ---------------------------------------------------------------------------
# global-norm clipping
# ---------------------------------------------------------------------------
def global_norm(tree) -> Array:
    leaves = [jnp.sum(jnp.square(l.astype(jnp.float32)))
              for l in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves))) if leaves else jnp.zeros(())


def clip_by_global_norm(tree, max_norm: float):
    g = global_norm(tree)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(g, 1e-9))
    return jax.tree.map(lambda l: l * scale.astype(l.dtype), tree), g


# ---------------------------------------------------------------------------
# optimizers
# ---------------------------------------------------------------------------
class SGDState(NamedTuple):
    step: Array
    momentum: Any


def sgd(lr: Schedule | float, momentum: float = 0.9,
        clip_norm: Optional[float] = None) -> Optimizer:
    sched = lr if callable(lr) else constant(lr)

    def init(params):
        return SGDState(jnp.zeros((), jnp.int32),
                        jax.tree.map(jnp.zeros_like, params))

    def update(grads, state, params=None):
        if clip_norm is not None:
            grads, _ = clip_by_global_norm(grads, clip_norm)
        mom = jax.tree.map(lambda m, g: momentum * m + g, state.momentum, grads)
        lr_t = sched(state.step)
        updates = jax.tree.map(lambda m: -lr_t.astype(m.dtype) * m, mom)
        return updates, SGDState(state.step + 1, mom)

    return Optimizer(init, update)


class AdamWState(NamedTuple):
    step: Array
    m: Any
    v: Any


def adamw(lr: Schedule | float, b1: float = 0.9, b2: float = 0.999,
          eps: float = 1e-8, weight_decay: float = 0.0,
          clip_norm: Optional[float] = None,
          wd_mask: Optional[Callable] = None) -> Optimizer:
    """AdamW with decoupled weight decay. `wd_mask(path, leaf) -> bool`
    selects which leaves decay (default: every leaf with ndim >= 2)."""
    sched = lr if callable(lr) else constant(lr)

    def init(params):
        zeros = lambda p: jax.tree.map(
            lambda l: jnp.zeros(l.shape, jnp.float32), p)
        return AdamWState(jnp.zeros((), jnp.int32), zeros(params), zeros(params))

    def update(grads, state, params):
        if clip_norm is not None:
            grads, _ = clip_by_global_norm(grads, clip_norm)
        t = state.step + 1
        tf = t.astype(jnp.float32)
        bc1 = 1 - b1 ** tf
        bc2 = 1 - b2 ** tf
        g32 = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, state.m, g32)
        v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, state.v, g32)
        lr_t = sched(state.step)

        def upd(path, m_, v_, p):
            u = -(lr_t * (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps))
            decay = (wd_mask(path, p) if wd_mask is not None else p.ndim >= 2)
            if weight_decay and decay:
                u = u - lr_t * weight_decay * p.astype(jnp.float32)
            return u.astype(p.dtype)

        updates = jax.tree_util.tree_map_with_path(upd, m, v, params)
        return updates, AdamWState(t, m, v)

    return Optimizer(init, update)


# ---------------------------------------------------------------------------
# masking / application
# ---------------------------------------------------------------------------
def path_str(path) -> str:
    """'body/0/wq/s_w'-style string from a tree_map_with_path key path."""
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        else:
            parts.append(str(k))
    return "/".join(parts)


def indicator_only_mask(path, leaf) -> bool:
    """Trainable = the per-bit indicator banks (scale factors) only."""
    p = path_str(path)
    return p.endswith("s_w") or p.endswith("s_a")


def masked(opt: Optimizer, trainable: Callable) -> Optimizer:
    """Zero updates for leaves where trainable() is False. The inner
    optimizer sees only the trainable leaves (the frozen ones are None
    holes), so frozen leaves carry no optimizer state, never enter global-
    norm clipping, and cost no device memory beyond their zero update."""

    def split(tree):
        return jax.tree_util.tree_map_with_path(
            lambda path, x: x if trainable(path, x) else None, tree)

    def init(params):
        return opt.init(split(params))

    def update(grads, state, params):
        updates, state = opt.update(split(grads), state, split(params))
        updates = jax.tree.map(
            lambda u, p: jnp.zeros_like(p) if u is None else u, updates,
            params, is_leaf=lambda x: x is None)
        return updates, state

    return Optimizer(init, update, trainable)


def apply_updates(params, updates):
    return jax.tree.map(lambda p, u: p + u.astype(p.dtype), params, updates)
