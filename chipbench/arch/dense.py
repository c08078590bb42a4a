"""The dense GQA decoder: RMS norms, optional per-head q/k norms, RoPE, a
gated MLP, seven searched projections a layer, the pinned 8-bit embedding
and head (tied or not). ``qwen3-0.6b`` and ``yi-9b`` run it.

Its weights, its plain reference's layer, its kernels and its counts; what
every architecture shares is in ``weights.py``, ``reference.py``,
``counts.py`` and ``kernels.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import counts
import kernels
import reference
import weights
from reference import Num, kv_quant, proj, rms, rope

PROJ = ("wq", "wk", "wv", "wo", "mlp_wi", "mlp_wg", "mlp_wo")

KERNELS = {
    "quant_matmul": "matmul", "quant_matmul_w4": "matmul",
    "quant_matmul_planes": "matmul",
    "decode_attn_quant": "decode_attn", "decode_attn_quant_paged":
    "decode_attn", "flash_fwd_pallas": "prefill_attn"}


def model_config(raw: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig

    d, h = raw["hidden_size"], raw["num_attention_heads"]
    return ModelConfig(
        name=raw["name"], family="dense",
        n_layers=raw["num_hidden_layers"], d_model=d, n_heads=h,
        n_kv_heads=raw["num_key_value_heads"],
        d_ff=raw["intermediate_size"], vocab=raw["vocab_size"],
        head_dim=raw.get("head_dim", d // h),
        rope_theta=float(raw["rope_theta"]), qk_norm=bool(raw["qk_norm"]),
        mlp_gated=True, act=raw["hidden_act"],
        tie_embeddings=bool(raw["tie_word_embeddings"]),
        norm_eps=float(raw["rms_norm_eps"]))


def dims(raw: dict):
    d, H = raw["hidden_size"], raw["num_attention_heads"]
    hd = raw.get("head_dim", d // H)
    return d, H, raw["num_key_value_heads"], hd, raw["intermediate_size"]


def proj_shapes(raw: dict):
    d, H, KV, hd, ff = dims(raw)
    return {"wq": (d, H * hd), "wk": (d, KV * hd), "wv": (d, KV * hd),
            "wo": (H * hd, d), "mlp_wi": (d, ff), "mlp_wg": (d, ff),
            "mlp_wo": (ff, d)}


# -- weights -----------------------------------------------------------------
def layer(key, raw: dict):
    """One decoder layer's float32 weights, in the program's layout."""
    shapes = proj_shapes(raw)
    d, _, _, hd, _ = dims(raw)
    ks = jax.random.split(key, len(PROJ))
    out = {"norm1": {"scale": jnp.ones((d,), jnp.float32)},
           "norm2": {"scale": jnp.ones((d,), jnp.float32)}}
    for k, name in zip(ks, PROJ):
        out[name] = weights.projection(k, *shapes[name])
    if raw["qk_norm"]:
        out["q_norm"] = jnp.ones((hd,), jnp.float32)
        out["k_norm"] = jnp.ones((hd,), jnp.float32)
    return out


def model_params(seed: int, raw: dict):
    return weights.lm_params(seed, raw, layer)


# -- the plain reference -----------------------------------------------------
def attend(x, p, wb, ab, raw, num):
    """The attention half of a layer, its residual added: causal GQA over
    int8 key and value rows. ``p`` is in ``num``'s dtype."""
    S, d = x.shape
    H, KV = raw["num_attention_heads"], raw["num_key_value_heads"]
    hd = raw.get("head_dim", d // H)
    eps = float(raw["rms_norm_eps"])
    dtype = num.dtype
    h = rms(x, p["norm1"]["scale"], eps)
    q = proj(h, p["wq"], wb[0], ab[0], num).reshape(S, H, hd)
    k = proj(h, p["wk"], wb[1], ab[1], num).reshape(S, KV, hd)
    v = proj(h, p["wv"], wb[2], ab[2], num).reshape(S, KV, hd)
    if raw["qk_norm"]:
        q = rms(q, p["q_norm"], eps)
        k = rms(k, p["k_norm"], eps)
    pos = jnp.arange(S)
    q = rope(q, pos, float(raw["rope_theta"]))
    k = kv_quant(rope(k, pos, float(raw["rope_theta"])))
    v = kv_quant(v)
    G = H // KV
    qg = q.reshape(S, KV, G, hd) * jnp.asarray(hd ** -0.5, dtype)
    logits = num.einsum("qkgd,skd->kgqs", qg, k, jnp.float32)
    mask = pos[None, :] <= pos[:, None]
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(dtype)
    o = num.einsum("kgqs,skd->qkgd", probs, v).reshape(S, H * hd)
    return x + proj(o, p["wo"], wb[3], ab[3], num)


def _layer(x, p, wb, ab, raw, num):
    p = jax.tree.map(lambda a: a.astype(num.dtype), p)
    x = attend(x, p, wb, ab, raw, num)
    h = rms(x, p["norm2"]["scale"], float(raw["rms_norm_eps"]))
    up = proj(h, p["mlp_wi"], wb[4], ab[4], num)
    gate = proj(h, p["mlp_wg"], wb[5], ab[5], num)
    return x + proj(jax.nn.silu(gate) * up, p["mlp_wo"], wb[6], ab[6], num)


@functools.partial(jax.jit,
                   static_argnames=("raw_items", "precision", "n_out"))
def _gap_rows(seed_key, tokens, start, wb, ab, *, raw_items, precision,
              n_out):
    """Logits at positions ``start .. start + n_out`` of one sequence."""
    return reference.lm_rows(seed_key, tokens, start, wb, ab, dict(raw_items),
                             Num(precision), n_out, layer, _layer)


def score(seed: int, raw: dict, policy: dict, seqs, cap: int, n_out: int,
          controls=()):
    return reference.teacher_forced(_gap_rows, PROJ, seed, raw, policy, seqs,
                                    cap, n_out, controls)


# -- counts ------------------------------------------------------------------
def decode_attn(raw: dict, rows: int, queries: int):
    """One layer's fused decode attention over int8 KV: ``queries`` query
    rows (one per live slot) attending ``rows`` cached rows in all. Codes are
    one byte, step sizes four bytes per row and head, positions four bytes
    per row; q and out are float32."""
    d, H, KV, hd, _ = dims(raw)
    ops = 4.0 * (H // KV) * KV * hd * rows
    nbytes = rows * (2 * KV * hd + 2 * 4 * KV + 4) + queries * 2 * 4 * H * hd
    return ops, float(nbytes)


def flash_fwd(raw: dict, s: int):
    """One layer's causal flash forward over an ``s``-token prompt, float32
    q, k, v and out."""
    d, H, KV, hd, _ = dims(raw)
    ops = 4.0 * H * hd * s * (s + 1) / 2
    nbytes = 4 * s * hd * (2 * H + 2 * KV)
    return ops, float(nbytes)


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
            total += kernels.least_s(o, b, peaks["int8_ops"],
                                     peaks["hbm_bytes_per_s"])
    return total


def decode_need_s(raw, policy, lens, peaks):
    """Least seconds of one decode launch by kernel class: the packed
    matmuls at one row per live slot, and every layer's decode attention
    over the rows the live slots attend (every row each slot holds)."""
    o, b = decode_attn(raw, sum(lens), len(lens))
    return {"matmul": quant_matmul_least_s(raw, policy, len(lens), peaks),
            "decode_attn": raw["num_hidden_layers"] * kernels.least_s(
                o, b, peaks["bf16_flops"], peaks["hbm_bytes_per_s"])}


def matmul_params(raw: dict) -> float:
    return float(sum(k * n for k, n in proj_shapes(raw).values())
                 * raw["num_hidden_layers"])


def token_flops(raw: dict, context: int) -> float:
    """Model FLOPs of one generated token at ``context`` attended rows:
    every projection, the head and attention."""
    d, H, KV, hd, _ = dims(raw)
    L = raw["num_hidden_layers"]
    return (2 * matmul_params(raw) + 2 * d * raw["vocab_size"]
            + 4 * L * H * hd * context)


def decode_flops(raw: dict, lens) -> float:
    """Model FLOPs of one decode launch whose live slots attend ``lens``
    rows."""
    per_tok = token_flops(raw, 0)
    att = token_flops(raw, 1) - per_tok
    return len(lens) * per_tok + sum(lens) * att


def prefill_flops(raw: dict, s: int) -> float:
    """Model FLOPs of prefilling an ``s``-token prompt (causal attention,
    the head at the last position only)."""
    d, H, KV, hd, _ = dims(raw)
    L = raw["num_hidden_layers"]
    return (2 * matmul_params(raw) * s + 4 * L * H * hd * s * (s + 1) / 2
            + 2 * d * raw["vocab_size"])
