"""Op-level parity of the Pallas routes with their dequant-fp reference.

A kernel-route serving run cannot be held to the fake-quant reference token
for token: int32 accumulation puts an activation on the neighbouring code
now and then, and with random weights that flip cascades through every
later layer. Its end-to-end logit drift (``launch.serve.RefScorer``) is
then mostly the cascade, and a subtle kernel fault hides inside it: a
K-scale block read from its neighbour, a decode mask one position short or
two swapped position blocks all stay under that bound (PERF.md). One op at
a time there is no cascade: each routed op runs once on its kernel route
and once on ``dequant-fp`` at matmul precision 'highest', over the same
inputs, and the two must agree to rounding.

``kernel_parity`` covers every distinct packed-matmul kernel of a session,
and decode attention plus the speculative verify on an int8 cache of the
serving layout, each slot filled to a different length.
"""
from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import attention as attn
from repro.runtime import dispatch, packing
from repro.runtime import kv_cache as qkv

# Largest max|kernel - reference| / max|reference| an op may show. Sound
# kernels agree to rounding: ~1e-7 interpreted on the CPU, and a TPU dot
# that rounds f32 operands to bf16 in one MXU pass would read ~5e-3. A
# K-scale block read from its neighbour, a decode mask one position short
# and two swapped position blocks read 0.25, 1.2 and 1.4 on the CPU
# (tests/test_kernel_faults.py).
PARITY_BOUND = 0.02

_EQN = "bk,kn->bn"


def _rel(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def matmul_parity(params, ctx, seed: int = 0) -> Dict[str, float]:
    """One reading per distinct (route, weight bits, K x N) among the packed
    layers the current dispatch resolves to a kernel, on activations spread
    over the layer's whole code grid."""
    r = np.random.default_rng(seed)
    out = {}
    for pl in packing.packed_leaves(params):
        if len(pl.shape) != 2:
            continue
        route = dispatch.resolve(_EQN, pl)
        key = f"matmul.{route}.w{pl.w_bits}.{pl.shape[0]}x{pl.shape[1]}"
        if route == "dequant-fp" or key in out:
            continue
        qmin, qmax = pl.a_range
        s = float(np.asarray(pl.s_a).reshape(-1)[0])
        x = jnp.asarray(r.uniform(qmin - 1, qmax + 1, (8, pl.shape[0])) * s,
                        jnp.float32)
        got = jax.jit(partial(dispatch.packed_qeinsum, _EQN, impl=route,
                              ctx=ctx))(x, pl)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(partial(dispatch.packed_qeinsum, _EQN,
                                   impl="dequant-fp", ctx=ctx))(x, pl)
        out[key] = _rel(got, want)
    return out


def _caches(r, slots, cap, kv, hd, page_size, lens):
    """A per-slot int8 ring cache with slot ``b`` holding positions
    ``0 .. lens[b] - 1`` and the same rows in the paged layout, its pages
    scattered over the pool in a random order."""
    k = jnp.asarray(r.normal(size=(slots, cap, kv, hd)), jnp.float32)
    v = jnp.asarray(r.normal(size=(slots, cap, kv, hd)), jnp.float32)
    kq, ks = qkv.quantize_rows(k)
    vq, vs = qkv.quantize_rows(v)
    t = np.arange(cap)
    pos = np.where(t[None] < np.asarray(lens)[:, None], t[None], -1)
    ring = qkv.QuantKVCache(kq, vq, ks, vs, jnp.asarray(pos, jnp.int32))
    per = cap // page_size
    order = r.permutation(slots * per)
    inv = np.argsort(order)

    def pages(a):
        a = a.reshape((slots * per, page_size) + a.shape[2:])
        return a[inv]

    paged = qkv.PagedKVCache(
        pages(kq), pages(vq), pages(ks), pages(vs), pages(ring.pos),
        jnp.asarray(order.reshape(slots, per), jnp.int32))
    return ring, paged


def attention_parity(layout: str, *, slots: int, cap: int, kv_heads: int,
                     heads: int, hd: int, page_size: int, verify_len: int,
                     seed: int = 0) -> Dict[str, float]:
    """Decode attention, and the speculative verify when ``verify_len`` > 0,
    on the route dispatch resolves for the int8 cache, against dequant-fp.
    Empty when that route is dequant-fp."""
    route = dispatch.resolve_decode_attn()
    if route == "dequant-fp":
        return {}
    r = np.random.default_rng(seed)
    cap = -(-cap // page_size) * page_size
    top = cap - max(verify_len, 1)
    lens = np.linspace(max(top // 4, 1), top, slots).astype(np.int32)
    ring, paged = _caches(r, slots, cap, kv_heads, hd, page_size, lens)
    cache = paged if layout == "paged" else ring

    def both(fn, s):
        q = jnp.asarray(r.normal(size=(slots, s, heads, hd)), jnp.float32)
        kn = jnp.asarray(r.normal(size=(slots, s, kv_heads, hd)), jnp.float32)
        vn = jnp.asarray(r.normal(size=(slots, s, kv_heads, hd)), jnp.float32)
        pos = jnp.asarray(lens[:, None] + np.arange(s)[None], jnp.int32)
        if s == 1:
            pos = pos[:, 0]
        step = jax.jit(lambda c: fn(q, c, kn, vn, pos, window=None)[0])
        got = step(cache)
        with dispatch.force_decode_attn("dequant-fp"), \
                jax.default_matmul_precision("highest"):
            want = jax.jit(lambda c: fn(q, c, kn, vn, pos, window=None)[0])(
                cache)
        return _rel(got, want)

    out = {f"decode_attn.{route}.{layout}": both(attn.decode_attention, 1)}
    if verify_len:
        out[f"verify_attn.{route}.{layout}"] = both(attn.verify_attention,
                                                    verify_len)
    return out


def kernel_parity(session, ctx, *, layout: str, slots: int, cap: int,
                  page_size: int, verify_len: int = 0) -> Dict[str, float]:
    """Every kernel route ``session`` serves with, each held against its
    dequant-fp reference on the same inputs: {op: relative error}."""
    cfg = session.cfg
    out = matmul_parity(session.params, ctx)
    out.update(attention_parity(
        layout, slots=slots, cap=cap, kv_heads=cfg.n_kv_heads,
        heads=cfg.n_heads, hd=cfg.hd, page_size=page_size,
        verify_len=verify_len))
    return out
