"""Plain reference of a served configuration, in ``jax.numpy``: the parts
that every architecture shares.

It imports nothing of the program. An architecture module
(``arch/<arch>.py``) writes its layer equations with these pieces and
scores served sequences with ``teacher_forced``. What every architecture
here shares: every searched projection's weights and input activations
quantized to the policy's bits with per-tensor step sizes (``proj``), RMS
norms, RoPE (half rotation), the 8-bit pinned embedding and head, and an
int8 KV cache with one step size per token row and head, modelled by
quantizing each key and value row once, as it is written (``kv_quant``).

The weights are drawn again from the seed, layer by layer, inside one scan
(``lm_rows``), so the whole model is never held at once.

``precision`` names the precision of every value and matmul. ``float32``
is the reference proper (matmuls at ``highest``). ``bfloat16``, the
precision below the configuration's, computes every value in bfloat16;
``float8_e4m3fn`` computes values in bfloat16 and rounds every matmul
operand to float8 (e4m3).
"""
from __future__ import annotations

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


def policy_arrays(raw: dict, policy: dict, projs):
    """(n_layers, len(projs)) weight bits and activation bits in ``projs``
    order, from the policy file's per-layer maps."""
    n = raw["num_hidden_layers"]
    wb = [[policy["w_bits"][f"L{i:03d}.{p}"] for p in projs]
          for i in range(n)]
    ab = [[policy["a_bits"][f"L{i:03d}.{p}"] for p in projs]
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


def proj(x, p, wbits, abits, num):
    """A searched projection: weight and input fake-quantized to their
    bits with the step sizes of ``p``'s bank."""
    bank = jnp.asarray(weights.BITS, jnp.int32)
    s_w = p["s_w"][jnp.argmax(bank == wbits)]
    s_a = p["s_a"][jnp.argmax(bank == abits)]
    w = fake_quant(p["w"].astype(num.dtype), s_w, wbits.astype(num.dtype))
    xq = fake_quant(x, s_a, abits.astype(num.dtype))
    return num.einsum("sd,de->se", xq, w)


def rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    f = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(f)[:, None, :], jnp.sin(f)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    cos, sin = cos.astype(x.dtype), sin.astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def embed_table(p, dtype):
    return fake_quant(p["w"].astype(dtype), p["s_w8"], 8.0)


def lm_rows(seed_key, tokens, start, wb, ab, raw, num, n_out, draw, apply):
    """Logits at positions ``start .. start + n_out`` of one sequence: the
    pinned 8-bit embedding, every layer drawn again by ``draw(key, raw)``
    and applied by ``apply(x, p, wb, ab, raw, num)`` inside one scan, the
    final RMS norm and the pinned 8-bit head (tied or not)."""
    dtype = num.dtype
    k_embed, k_head, k_layers = seed_key
    emb = weights.embed(k_embed, raw)
    x = jnp.take(embed_table(emb, dtype), tokens, axis=0)

    def body(x, xs):
        key, wbl, abl = xs
        return apply(x, draw(key, raw), wbl, abl, raw, num), None

    x, _ = jax.lax.scan(body, x, (k_layers, wb, ab))
    x = rms(x, jnp.ones((x.shape[-1],), dtype), float(raw["rms_norm_eps"]))
    rows = jax.lax.dynamic_slice_in_dim(x, start, n_out, axis=0)
    if raw["tie_word_embeddings"]:
        logits = num.einsum("sd,vd->sv", rows, embed_table(emb, dtype),
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


def teacher_forced(gap_rows, projs, seed: int, raw: dict, policy: dict,
                   seqs, cap: int, n_out: int, controls=()):
    """Teacher-forced reference over served sequences.

    ``gap_rows(seed_key, tokens, start, wb, ab, *, raw_items, precision,
    n_out)`` is an architecture's jitted logits at positions ``start ..
    start + n_out`` of one sequence; ``projs`` orders the policy's bits.
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
    wb, ab = policy_arrays(raw, policy, projs)
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
            return np.asarray(gap_rows(
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
