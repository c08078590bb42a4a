"""The dense harness as it stood before architecture modules, kept as the
oracle that ``test_dense_unchanged.py`` holds ``arch/dense.py`` to: the
layer weights and the whole tree (``weights.py``), the reference's layer,
logits and scoring loop (``reference.py``), the counts and the least time
of the packed matmuls (``counts.py``, ``kernels.py``), the operand rule
that told packed matmuls from attention, and the three device readers'
sums over ``(rows, live)`` a launch. Copied as they were; they call the
shared functions, which did not change.
"""
from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp

import counts
import kernels
import weights
from reference import Num, bucket, fake_quant, kv_quant, rms
from weights import BITS, embed, head, keys, stat_scale

PROJ = ("wq", "wk", "wv", "wo", "mlp_wi", "mlp_wg", "mlp_wo")


def proj_shapes(raw: dict):
    d, ff = raw["hidden_size"], raw["intermediate_size"]
    hd = raw.get("head_dim", d // raw["num_attention_heads"])
    qd = raw["num_attention_heads"] * hd
    kvd = raw["num_key_value_heads"] * hd
    return {"wq": (d, qd), "wk": (d, kvd), "wv": (d, kvd), "wo": (qd, d),
            "mlp_wi": (d, ff), "mlp_wg": (d, ff), "mlp_wo": (ff, d)}


def layer(key, raw: dict):
    """One decoder layer's float32 weights, in the program's layout."""
    shapes = proj_shapes(raw)
    d = raw["hidden_size"]
    hd = raw.get("head_dim", d // raw["num_attention_heads"])
    ks = jax.random.split(key, len(PROJ))
    out = {"norm1": {"scale": jnp.ones((d,), jnp.float32)},
           "norm2": {"scale": jnp.ones((d,), jnp.float32)}}
    for k, name in zip(ks, PROJ):
        fi, fo = shapes[name]
        w = jax.random.normal(k, (fi, fo), jnp.float32) * fi ** -0.5
        out[name] = {
            "w": w,
            "s_w": jnp.stack([stat_scale(w, 2 ** (b - 1) - 1) for b in BITS]),
            "s_a": jnp.asarray([0.1 / b for b in BITS], jnp.float32)}
    if raw["qk_norm"]:
        out["q_norm"] = jnp.ones((hd,), jnp.float32)
        out["k_norm"] = jnp.ones((hd,), jnp.float32)
    return out


def model_params(seed: int, raw: dict):
    """The whole parameter tree in the program's layout (layers stacked
    on a leading axis), made in one jitted call on the default device."""
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


def policy_arrays(raw: dict, policy: dict):
    """(n_layers, 7) weight bits and activation bits in ``PROJ``
    order, from the policy file's per-layer maps."""
    n = raw["num_hidden_layers"]
    wb = [[policy["w_bits"][f"L{i:03d}.{p}"] for p in PROJ]
          for i in range(n)]
    ab = [[policy["a_bits"][f"L{i:03d}.{p}"] for p in PROJ]
          for i in range(n)]
    return jnp.asarray(wb, jnp.int32), jnp.asarray(ab, jnp.int32)


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
        return _layer(x, layer(key, raw), wbl, abl, raw, num), None

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


def dims(raw: dict):
    d, H = raw["hidden_size"], raw["num_attention_heads"]
    hd = raw.get("head_dim", d // H)
    return d, H, raw["num_key_value_heads"], hd, raw["intermediate_size"]


def decode_attn(raw: dict, rows: int, queries: int):
    """One layer's fused decode attention over int8 KV: ``queries`` query
    rows (one per live slot) attending ``rows`` cached rows in all. Codes are
    one byte, step sizes four bytes per row and head, positions four bytes
    per row; q and out are float32."""
    d, H, KV, hd, _ = dims(raw)
    ops = 4.0 * (H // KV) * KV * hd * rows
    nbytes = rows * (2 * KV * hd + 2 * 4 * KV + 4) + queries * 2 * 4 * H * hd
    return ops, float(nbytes)


def matmul_params(raw: dict) -> float:
    return float(sum(k * n for k, n in proj_shapes(raw).values())
                 * raw["num_hidden_layers"])


def decode_flops(raw: dict, context: int) -> float:
    """Model FLOPs of one generated token at ``context`` attended rows:
    every projection, the head and attention."""
    d, H, KV, hd, _ = dims(raw)
    L = raw["num_hidden_layers"]
    return (2 * matmul_params(raw) + 2 * d * raw["vocab_size"]
            + 4 * L * H * hd * context)


_QMM = re.compile(r"custom-call\(s8\[\d+,\d+\]\{[^}]*\}[^,]*, [su]8\[\d+,\d+\]")


def operand_matmul(op) -> bool:
    """The int8 and packed-int4 matmul kernels: an int8 (m, k) activation
    operand and a (k, n) or (k/2, n) one-byte weight operand."""
    return kernels.custom(op) and bool(_QMM.search(op.name))


def operand_attention(op) -> bool:
    """The attention kernels: every other Pallas kernel in the serving
    programs (decode attention in the decode program, flash forward in the
    prefill program)."""
    return kernels.custom(op) and not operand_matmul(op)


def least_s(ops: float, nbytes: float, peak_ops: float, bw: float) -> float:
    return max(ops / peak_ops, nbytes / bw)


def quant_matmul_least_s(raw, policy, m: int, peaks) -> float:
    """Least time of every searched projection's packed matmul at ``m``
    rows, each at its own roofline."""
    total = 0.0
    shapes = proj_shapes(raw)
    for i in range(raw["num_hidden_layers"]):
        for p in PROJ:
            k, n = shapes[p]
            o, b = counts.quant_matmul(m, k, n,
                                       policy["w_bits"][f"L{i:03d}.{p}"])
            total += least_s(o, b, peaks["int8_ops"],
                             peaks["hbm_bytes_per_s"])
    return total


def readers(raw, policy, decodes, peaks, red):
    """``quant_matmul_roofline.decode``, ``decode_attn_roofline`` and
    ``mfu.decode`` over a reduction, each launch recorded as (rows,
    live)."""
    t = red.kernel_s(lambda o: kernels.in_program(o, "jit_decode")
                     and operand_matmul(o))
    need = sum(quant_matmul_least_s(raw, policy, live, peaks)
               for _, live in decodes)
    qmm = kernels.share(need, t)
    t = red.kernel_s(lambda o: kernels.in_program(o, "jit_decode")
                     and operand_attention(o))
    L = raw["num_hidden_layers"]
    need = 0.0
    for rows, live in decodes:
        o, b = decode_attn(raw, rows, live)
        need += L * least_s(o, b, peaks["bf16_flops"],
                            peaks["hbm_bytes_per_s"])
    attn = kernels.share(need, t)
    per_tok = decode_flops(raw, 0)
    att = decode_flops(raw, 1) - per_tok
    flops = sum(live * per_tok + rows * att for rows, live in decodes)
    mfu = 100.0 * flops / (red.window_s * peaks["bf16_flops"])
    return {"quant_matmul_roofline.decode": qmm,
            "decode_attn_roofline": attn, "mfu.decode": mfu}
