"""Seeded weights, made on the device: the parts that every architecture
shares.

The benchmark makes its own inputs: the weights are drawn from ``--seed``
and handed to the program in its parameter layout. An architecture module
(``arch/<arch>.py``) draws its own layers with ``projection`` and builds
the whole tree with ``lm_params``; the plain reference draws each layer
again from the same key, so it takes nothing that the program made.

Draws follow the usual statistics init of the repo's models: a projection is
``N(0, 1) / sqrt(fan_in)``, its weight step sizes per bit width are
``2 E|w| / sqrt(qmax_b)`` and its activation step sizes ``0.1 / b``; the
pinned 8-bit embedding and head use ``2 E|w| / sqrt(127)`` and ``0.1 / 8``;
norms are ones.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

BITS = (2, 3, 4, 5, 6)


def root_key(seed: int):
    """A key for any whole seed, also one beyond 32 bits."""
    seed = int(seed)
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def keys(seed: int, n_layers: int):
    """(embed key, head key, (n_layers, 2) layer keys)."""
    ks = jax.random.split(root_key(seed), n_layers + 2)
    return ks[0], ks[1], ks[2:]


def stat_scale(w, qmax):
    return 2.0 * jnp.mean(jnp.abs(w)) / jnp.sqrt(jnp.float32(qmax))


def projection(key, fan_in: int, fan_out: int):
    """One searched projection: its float32 weight and its bank of weight
    and activation step sizes, one per width of ``BITS``."""
    w = jax.random.normal(key, (fan_in, fan_out), jnp.float32) * fan_in ** -0.5
    return {"w": w,
            "s_w": jnp.stack([stat_scale(w, 2 ** (b - 1) - 1) for b in BITS]),
            "s_a": jnp.asarray([0.1 / b for b in BITS], jnp.float32)}


def embed(key, raw: dict):
    v, d = raw["vocab_size"], raw["hidden_size"]
    w = jax.random.normal(key, (v, d), jnp.float32) * d ** -0.5
    return {"w": w, "s_w8": stat_scale(w, 127)}


def head(key, raw: dict):
    v, d = raw["vocab_size"], raw["hidden_size"]
    w = jax.random.normal(key, (d, v), jnp.float32) * d ** -0.5
    return {"w": w, "s_w8": stat_scale(w, 127),
            "s_a8": jnp.asarray(0.1 / 8, jnp.float32)}


def lm_params(seed: int, raw: dict, layer):
    """The whole parameter tree in the program's layout, every layer drawn
    by ``layer(key, raw)`` and stacked on a leading axis, made in one
    jitted call on the default device."""
    n = raw["num_hidden_layers"]

    def build(k_embed, k_head, k_layers):
        p = {"embed": embed(k_embed, raw), "prefix": {}, "suffix": {},
             "body": {"0": jax.vmap(lambda k: layer(k, raw))(k_layers)},
             "final_norm": {"scale": jnp.ones((raw["hidden_size"],),
                                              jnp.float32)}}
        if raw["tie_word_embeddings"]:
            p["head"] = {"s_a8": jnp.asarray(0.1 / 8, jnp.float32)}
        else:
            p["head"] = head(k_head, raw)
        return p

    return jax.jit(build)(*keys(seed, n))
