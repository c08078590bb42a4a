"""Plain reference of a served configuration, in ``jax.numpy``.

It imports nothing of the program. It computes the model the configuration
and its policy file state: a dense GQA decoder with RMS norms, optional
per-head q/k norms, RoPE (half rotation), a gated SiLU MLP, every searched
projection's weights and input activations quantized to the policy's bits
with per-tensor step sizes, the 8-bit pinned embedding and head, and an int8
KV cache with one step size per token row and head. The cache is modelled by
quantizing each key and value row once, as it is written.

The weights are drawn again from the seed, layer by layer (``weights.py``),
inside one scan, so the whole model is never held at once.

``precision`` names the precision of every value and matmul. ``float32``
is the reference proper (matmuls at ``highest``). ``bfloat16``, the
precision below the configuration's, computes every value in bfloat16;
``float8_e4m3fn`` computes values in bfloat16 and rounds every matmul
operand to float8 (e4m3).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import weights

KV_QMAX = 127.0


def qrange(bits):
    return -(2.0 ** (bits - 1)), 2.0 ** (bits - 1) - 1


def fake_quant(v, s, bits):
    lo, hi = qrange(bits)
    s = jnp.maximum(s.astype(v.dtype), jnp.asarray(1e-9, v.dtype))
    return jnp.round(jnp.clip(v / s, lo, hi)) * s


def kv_quant(x):
    """One step size per row and head: max |x| / 127."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True) / KV_QMAX,
                    jnp.asarray(1e-8, x.dtype))
    return jnp.clip(jnp.round(x / s), -KV_QMAX, KV_QMAX) * s


def rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def policy_arrays(raw: dict, policy: dict):
    """(n_layers, 7) weight bits and activation bits in ``weights.PROJ``
    order, from the policy file's per-layer maps."""
    n = raw["num_hidden_layers"]
    wb = [[policy["w_bits"][f"L{i:03d}.{p}"] for p in weights.PROJ]
          for i in range(n)]
    ab = [[policy["a_bits"][f"L{i:03d}.{p}"] for p in weights.PROJ]
          for i in range(n)]
    return jnp.asarray(wb, jnp.int32), jnp.asarray(ab, jnp.int32)


class Num:
    """The precision of one reference run."""

    def __init__(self, precision: str):
        self.name = precision
        self.dtype = jnp.float32 if precision == "float32" else jnp.bfloat16
        self.prec = (jax.lax.Precision.HIGHEST if precision == "float32"
                     else jax.lax.Precision.DEFAULT)
        self.fp8 = precision == "float8_e4m3fn"

    def op(self, x):
        """A matmul operand."""
        if self.fp8:
            return x.astype(jnp.float8_e4m3fn).astype(self.dtype)
        return x

    def einsum(self, eq, a, b, out=None):
        return jnp.einsum(eq, self.op(a), self.op(b), precision=self.prec,
                          preferred_element_type=out or self.dtype)


def _proj(x, p, wbits, abits, num):
    bank = jnp.asarray(weights.BITS, jnp.int32)
    s_w = p["s_w"][jnp.argmax(bank == wbits)]
    s_a = p["s_a"][jnp.argmax(bank == abits)]
    w = fake_quant(p["w"].astype(num.dtype), s_w, wbits.astype(num.dtype))
    xq = fake_quant(x, s_a, abits.astype(num.dtype))
    return num.einsum("sd,de->se", xq, w)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    f = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(f)[:, None, :], jnp.sin(f)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    cos, sin = cos.astype(x.dtype), sin.astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, p, wb, ab, raw, num):
    S, d = x.shape
    H, KV = raw["num_attention_heads"], raw["num_key_value_heads"]
    hd = raw.get("head_dim", d // H)
    eps = float(raw["rms_norm_eps"])
    dtype = num.dtype
    p = jax.tree.map(lambda a: a.astype(dtype), p)
    h = rms(x, p["norm1"]["scale"], eps)
    q = _proj(h, p["wq"], wb[0], ab[0], num).reshape(S, H, hd)
    k = _proj(h, p["wk"], wb[1], ab[1], num).reshape(S, KV, hd)
    v = _proj(h, p["wv"], wb[2], ab[2], num).reshape(S, KV, hd)
    if raw["qk_norm"]:
        q = rms(q, p["q_norm"], eps)
        k = rms(k, p["k_norm"], eps)
    pos = jnp.arange(S)
    q = _rope(q, pos, float(raw["rope_theta"]))
    k = kv_quant(_rope(k, pos, float(raw["rope_theta"])))
    v = kv_quant(v)
    G = H // KV
    qg = q.reshape(S, KV, G, hd) * jnp.asarray(hd ** -0.5, dtype)
    logits = num.einsum("qkgd,skd->kgqs", qg, k, jnp.float32)
    mask = pos[None, :] <= pos[:, None]
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(dtype)
    o = num.einsum("kgqs,skd->qkgd", probs, v).reshape(S, H * hd)
    x = x + _proj(o, p["wo"], wb[3], ab[3], num)
    h = rms(x, p["norm2"]["scale"], eps)
    up = _proj(h, p["mlp_wi"], wb[4], ab[4], num)
    gate = _proj(h, p["mlp_wg"], wb[5], ab[5], num)
    return x + _proj(jax.nn.silu(gate) * up, p["mlp_wo"], wb[6], ab[6], num)


def _embed_table(p, dtype):
    return fake_quant(p["w"].astype(dtype), p["s_w8"], 8.0)


@functools.partial(jax.jit,
                   static_argnames=("raw_items", "precision", "n_out"))
def _gap_rows(seed_key, tokens, start, wb, ab, *, raw_items, precision,
              n_out):
    """Logits at positions ``start .. start + n_out`` of one sequence."""
    raw = dict(raw_items)
    num = Num(precision)
    dtype = num.dtype
    k_embed, k_head, k_layers = seed_key
    emb = weights.embed(k_embed, raw)
    x = jnp.take(_embed_table(emb, dtype), tokens, axis=0)

    def body(x, xs):
        key, wbl, abl = xs
        return _layer(x, weights.layer(key, raw), wbl, abl, raw, num), None

    x, _ = jax.lax.scan(body, x, (k_layers, wb, ab))
    x = rms(x, jnp.ones((x.shape[-1],), dtype), float(raw["rms_norm_eps"]))
    rows = jax.lax.dynamic_slice_in_dim(x, start, n_out, axis=0)
    if raw["tie_word_embeddings"]:
        logits = num.einsum("sd,vd->sv", rows, _embed_table(emb, dtype),
                            jnp.float32)
    else:
        hp = weights.head(k_head, raw)
        xq = fake_quant(rows, hp["s_a8"], 8.0)
        w = fake_quant(hp["w"].astype(dtype), hp["s_w8"], 8.0)
        logits = num.einsum("sd,dv->sv", xq, w, jnp.float32)
    return logits.astype(jnp.float32)


def bucket(need: int, cap: int) -> int:
    """The padded length a sequence of ``need`` rows is scored at: the next
    power of two, at most ``cap``."""
    if need > cap:
        raise ValueError(f"{need} rows do not fit the cache of {cap}")
    b = 256
    while b < need:
        b *= 2
    return min(b, cap)


def score(seed: int, raw: dict, policy: dict, seqs, cap: int, n_out: int,
          controls=()):
    """Teacher-forced reference over served sequences.

    ``seqs`` is a list of (prompt tokens, served tokens, row indices). For
    each served token the reference reads the gap by which its logit lies
    below the reference's best at that position; for each listed row index
    ``j`` it returns its whole row of logits, the one that served token
    ``j`` was picked from. For each precision in ``controls`` the same
    reference is run in that precision over the same tokens: it reads the
    gap of the token that it puts first, and returns its own rows.

    Returns {"program": [gaps], "rows": [ref rows], <control>: {"gaps":
    [gaps], "rows": [rows]}}, rows in the order of ``seqs`` and indices.
    """
    import numpy as np

    ks = weights.keys(seed, raw["num_hidden_layers"])
    wb, ab = policy_arrays(raw, policy)
    raw_items = tuple(sorted((k, v) for k, v in raw.items()
                             if isinstance(v, (int, float, str, bool))))
    out = {"program": [], "rows": []}
    out.update({c: {"gaps": [], "rows": []} for c in controls})
    for prompt, served, idx in seqs:
        prompt = np.asarray(prompt, np.int32)
        served = np.asarray(served, np.int32)
        n = len(served)
        length = bucket(len(prompt) + n_out, cap)
        toks = np.zeros((length,), np.int32)
        seq = np.concatenate([prompt, served[:-1]])
        toks[: len(seq)] = seq
        start = len(prompt) - 1

        def rows(precision):
            return np.asarray(_gap_rows(
                ks, jnp.asarray(toks), start, wb, ab, raw_items=raw_items,
                precision=precision, n_out=n_out))[:n]

        ref = rows("float32")
        best = ref.max(-1)
        out["program"] += list(best - ref[np.arange(n), served])
        out["rows"] += [ref[j] for j in idx]
        for c in controls:
            low = rows(c)
            pick = low.argmax(-1)
            out[c]["gaps"] += list(best - ref[np.arange(n), pick])
            out[c]["rows"] += [low[j] for j in idx]
    return out
