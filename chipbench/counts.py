"""Operations and bytes that each kernel's algorithm needs, from the layer's
shapes and the policy's bits, and the model FLOPs per token for ``mfu.*``.

The counts do not depend on which route the program took: a weight counts
its packed bytes at its policy width whether it was unpacked in XLA or in
the kernel, and attention counts only the rows that a query may attend.
"""
from __future__ import annotations

import math

PROJ = ("wq", "wk", "wv", "wo", "mlp_wi", "mlp_wg", "mlp_wo")


def dims(raw: dict):
    d, H = raw["hidden_size"], raw["num_attention_heads"]
    hd = raw.get("head_dim", d // H)
    return d, H, raw["num_key_value_heads"], hd, raw["intermediate_size"]


def proj_shapes(raw: dict):
    d, H, KV, hd, ff = dims(raw)
    return {"wq": (d, H * hd), "wk": (d, KV * hd), "wv": (d, KV * hd),
            "wo": (H * hd, d), "mlp_wi": (d, ff), "mlp_wg": (d, ff),
            "mlp_wo": (ff, d)}


def quant_matmul(m: int, k: int, n: int, w_bits: int):
    """(int8 operations, bytes) of one packed matmul: int8 activation codes
    (m, k) times a (k, n) weight at ``w_bits``, float32 out."""
    ops = 2.0 * m * k * n
    nbytes = math.ceil(k * n * w_bits / 8) + m * k + 4 * m * n + 8
    return ops, float(nbytes)


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


def prefill_flops(raw: dict, s: int) -> float:
    """Model FLOPs of prefilling an ``s``-token prompt (causal attention,
    the head at the last position only)."""
    d, H, KV, hd, _ = dims(raw)
    L = raw["num_hidden_layers"]
    return (2 * matmul_params(raw) * s + 4 * L * H * hd * s * (s + 1) / 2
            + 2 * d * raw["vocab_size"])
