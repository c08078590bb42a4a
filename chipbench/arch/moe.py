"""The fine-grained MoE decoder of DeepSeekMoE: the dense architecture's MHA
attention in every layer, ``first_k_dense_replace`` leading layers with the
dense gated MLP, then layers whose MLP is ``n_routed_experts`` routed experts
of width ``moe_intermediate_size`` (a float32 softmax router picks
``num_experts_per_tok``; its weights are renormalised only where
``norm_topk_prob``) beside ``n_shared_experts`` shared experts that act as
one always-on gated MLP of ``n_shared_experts`` times the width.
``deepseek-moe-16b`` runs it.

Its weights, its plain reference's layers, its kernels and its counts; the
attention, the dense layer and the packed-matmul counts are
``arch/dense.py``'s.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import archs
import counts
import kernels
import reference
import weights
from reference import Num, fake_quant, proj, rms

dense = archs.by_name("dense")

# the searched projections of a MoE layer, in the program's policy names
MOE_PROJ = ("wq", "wk", "wv", "wo", "moe.wi", "moe.wg", "moe.wo",
            "moe.shared_wi", "moe.shared_wg", "moe.shared_wo")
SLOTS = tuple(f"p{j}" for j in range(len(MOE_PROJ)))

KERNELS = dict(dense.KERNELS, quant_matmul_grouped="expert_matmul")


def model_config(raw: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig, MoEConfig

    d, h = raw["hidden_size"], raw["num_attention_heads"]
    return ModelConfig(
        name=raw["name"], family="moe",
        n_layers=raw["num_hidden_layers"], d_model=d, n_heads=h,
        n_kv_heads=raw["num_key_value_heads"],
        d_ff=raw["moe_intermediate_size"], vocab=raw["vocab_size"],
        head_dim=raw.get("head_dim", d // h),
        rope_theta=float(raw["rope_theta"]), mlp_gated=True,
        act=raw["hidden_act"],
        tie_embeddings=bool(raw["tie_word_embeddings"]),
        norm_eps=float(raw["rms_norm_eps"]),
        moe=MoEConfig(n_experts=raw["n_routed_experts"],
                      top_k=raw["num_experts_per_tok"],
                      n_shared=raw["n_shared_experts"],
                      d_ff=raw["moe_intermediate_size"],
                      first_dense_layers=raw["first_k_dense_replace"],
                      dense_d_ff=raw["intermediate_size"],
                      norm_topk=bool(raw["norm_topk_prob"])))


def dense_raw(raw: dict) -> dict:
    """The leading dense layers as the dense module reads them."""
    return dict(raw, qk_norm=False)


def dims(raw: dict):
    """(d, experts, experts per token, shared width, expert width)."""
    f = raw["moe_intermediate_size"]
    return (raw["hidden_size"], raw["n_routed_experts"],
            raw["num_experts_per_tok"], raw["n_shared_experts"] * f, f)


# -- weights -----------------------------------------------------------------
def experts(key, n: int, fan_in: int, fan_out: int):
    """A stack of ``n`` searched expert projections: each expert's weight
    and its own bank of weight step sizes; activation steps ``0.1 / b``."""
    w = (jax.random.normal(key, (n, fan_in, fan_out), jnp.float32)
         * fan_in ** -0.5)
    s_w = jnp.stack([2.0 * jnp.mean(jnp.abs(w), axis=(1, 2))
                     / jnp.sqrt(jnp.float32(2 ** (b - 1) - 1))
                     for b in weights.BITS], axis=-1)
    s_a = jnp.broadcast_to(
        jnp.asarray([0.1 / b for b in weights.BITS], jnp.float32),
        (n, len(weights.BITS)))
    return {"w": w, "s_w": s_w, "s_a": s_a}


def moe_layer(key, raw: dict):
    """One MoE layer's float32 weights, in the program's layout."""
    d, E, _, fs, f = dims(raw)
    shapes = dense.proj_shapes(raw)
    ks = jax.random.split(key, 11)
    out = {"norm1": {"scale": jnp.ones((d,), jnp.float32)},
           "norm2": {"scale": jnp.ones((d,), jnp.float32)}}
    for k, name in zip(ks[:4], ("wq", "wk", "wv", "wo")):
        out[name] = weights.projection(k, *shapes[name])
    out["moe"] = {
        "router": {"w": jax.random.normal(ks[4], (d, E), jnp.float32)
                   * d ** -0.5},
        "wi": experts(ks[5], E, d, f), "wg": experts(ks[6], E, d, f),
        "wo": experts(ks[7], E, f, d),
        "shared_wi": weights.projection(ks[8], d, fs),
        "shared_wg": weights.projection(ks[9], d, fs),
        "shared_wo": weights.projection(ks[10], fs, d)}
    return out


def model_params(seed: int, raw: dict):
    """The whole parameter tree in the program's layout (the leading dense
    layers unrolled, the MoE layers stacked), in one jitted call on the
    default device."""
    n, fd = raw["num_hidden_layers"], raw["first_k_dense_replace"]
    draw = dense_raw(raw)

    def build(k_embed, k_head, k_layers):
        return {
            "embed": weights.embed(k_embed, raw),
            "head": weights.head(k_head, raw),
            "prefix": {str(i): dense.layer(k_layers[i], draw)
                       for i in range(fd)},
            "body": {"0": jax.vmap(lambda k: moe_layer(k, raw))(
                k_layers[fd:])},
            "suffix": {},
            "final_norm": {"scale": jnp.ones((raw["hidden_size"],),
                                             jnp.float32)}}

    return jax.jit(build)(*weights.keys(seed, n))


# -- the plain reference -----------------------------------------------------
def expert_proj(x, p, e, wbits, abits, num):
    """Expert ``e``'s projection: its weight and the input fake-quantized
    to their bits with expert ``e``'s step sizes."""
    bank = jnp.asarray(weights.BITS, jnp.int32)
    s_w = p["s_w"][e][jnp.argmax(bank == wbits)]
    s_a = p["s_a"][e][jnp.argmax(bank == abits)]
    w = fake_quant(p["w"][e], s_w, wbits.astype(num.dtype))
    return num.einsum("sd,de->se", fake_quant(x, s_a, abits.astype(num.dtype)),
                      w)


def _moe_layer(x, p, wb, ab, raw, num):
    """Attention, then every token through the routed experts dropless:
    each expert over every row, weighted by the row's router weight for it
    (its softmax probability if among the top k, renormalised where the
    configuration says so, else zero), then the shared MLP."""
    p = jax.tree.map(lambda a: a.astype(num.dtype), p)
    x = dense.attend(x, p, wb, ab, dense_raw(raw), num)
    h = rms(x, p["norm2"]["scale"], float(raw["rms_norm_eps"]))
    m = p["moe"]
    _, E, k, _, _ = dims(raw)
    logits = jnp.einsum("sd,de->se", h.astype(jnp.float32),
                        m["router"]["w"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, k)
    if raw["norm_topk_prob"]:
        top_w = top_w / top_w.sum(-1, keepdims=True)
    gate = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], top_i].set(top_w).astype(num.dtype)

    def expert(out, e):
        up = expert_proj(h, m["wi"], e, wb[4], ab[4], num)
        g = expert_proj(h, m["wg"], e, wb[5], ab[5], num)
        y = expert_proj(jax.nn.silu(g) * up, m["wo"], e, wb[6], ab[6], num)
        return out + jax.lax.dynamic_slice_in_dim(gate, e, 1, axis=1) * y, None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(E))
    up = proj(h, m["shared_wi"], wb[7], ab[7], num)
    g = proj(h, m["shared_wg"], wb[8], ab[8], num)
    out = out + proj(jax.nn.silu(g) * up, m["shared_wo"], wb[9], ab[9], num)
    return x + out


def lm_rows(seed_key, tokens, start, wb, ab, raw, num, n_out):
    """Logits at positions ``start .. start + n_out`` of one sequence: the
    pinned 8-bit embedding, the dense layers, the MoE layers drawn again
    one by one inside a scan, the final RMS norm and the pinned 8-bit head
    (``reference.lm_rows`` for two kinds of layer)."""
    dtype = num.dtype
    k_embed, k_head, k_layers = seed_key
    fd = raw["first_k_dense_replace"]
    x = jnp.take(reference.embed_table(weights.embed(k_embed, raw), dtype),
                 tokens, axis=0)
    draw = dense_raw(raw)
    for i in range(fd):
        x = dense._layer(x, dense.layer(k_layers[i], draw), wb[i], ab[i],
                         draw, num)

    def body(x, xs):
        key, wbl, abl = xs
        return _moe_layer(x, moe_layer(key, raw), wbl, abl, raw, num), None

    x, _ = jax.lax.scan(body, x, (k_layers[fd:], wb[fd:], ab[fd:]))
    x = rms(x, jnp.ones((x.shape[-1],), dtype), float(raw["rms_norm_eps"]))
    rows = jax.lax.dynamic_slice_in_dim(x, start, n_out, axis=0)
    hp = weights.head(k_head, raw)
    xq = fake_quant(rows, hp["s_a8"], 8.0)
    w = fake_quant(hp["w"].astype(dtype), hp["s_w8"], 8.0)
    return num.einsum("sd,dv->sv", xq, w, jnp.float32).astype(jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("raw_items", "precision", "n_out"))
def _gap_rows(seed_key, tokens, start, wb, ab, *, raw_items, precision,
              n_out):
    return lm_rows(seed_key, tokens, start, wb, ab, dict(raw_items),
                   Num(precision), n_out)


def slot_policy(raw: dict, policy: dict) -> dict:
    """The policy's bits in one row of ``SLOTS`` a layer: a dense layer's
    seven projections in ``dense.PROJ`` order (its last slots unused), a MoE
    layer's ten in ``MOE_PROJ`` order."""
    out = {"w_bits": {}, "a_bits": {}}
    for i in range(raw["num_hidden_layers"]):
        names = (dense.PROJ if i < raw["first_k_dense_replace"]
                 else MOE_PROJ)
        for j, slot in enumerate(SLOTS):
            for kind in out:
                out[kind][f"L{i:03d}.{slot}"] = (
                    policy[kind][f"L{i:03d}.{names[j]}"]
                    if j < len(names) else 8)
    return out


def score(seed: int, raw: dict, policy: dict, seqs, cap: int, n_out: int,
          controls=()):
    return reference.teacher_forced(_gap_rows, SLOTS, seed, raw,
                                    slot_policy(raw, policy), seqs, cap,
                                    n_out, controls)


# -- counts ------------------------------------------------------------------
def experts_hit(raw: dict, tokens: int) -> float:
    """Distinct experts a MoE layer reads for ``tokens`` tokens, each
    picking its experts uniformly: E (1 - (1 - k/E)^T)."""
    _, E, k, _, _ = dims(raw)
    return E * (1.0 - (1.0 - k / E) ** tokens)


def moe_layers(raw: dict) -> range:
    return range(raw["first_k_dense_replace"], raw["num_hidden_layers"])


def matmul_shapes(raw: dict, i: int):
    """{policy name: (k, n)} of layer ``i``'s packed 2-D projections."""
    shapes = dense.proj_shapes(dense_raw(raw))
    attn = {p: shapes[p] for p in ("wq", "wk", "wv", "wo")}
    if i < raw["first_k_dense_replace"]:
        return dict(shapes)
    d, _, _, fs, _ = dims(raw)
    return dict(attn, **{"moe.shared_wi": (d, fs), "moe.shared_wg": (d, fs),
                         "moe.shared_wo": (fs, d)})


def expert_shapes(raw: dict):
    d, _, _, _, f = dims(raw)
    return {"moe.wi": (d, f), "moe.wg": (d, f), "moe.wo": (f, d)}


def expert_matmul_least_s(raw, policy, m: int, peaks) -> float:
    """Least time of every MoE layer's grouped expert matmuls for ``m``
    tokens: the int8 operations of their ``m k`` rows through each of the
    three projections, and the packed bytes of the experts they hit, with
    the rows' int8 codes in and float32 out."""
    _, _, k, _, _ = dims(raw)
    rows, hit = m * k, experts_hit(raw, m)
    total = 0.0
    for i in moe_layers(raw):
        ops = nbytes = 0.0
        for p, (kk, n) in expert_shapes(raw).items():
            o, b = counts.quant_matmul(rows, kk, n,
                                       policy["w_bits"][f"L{i:03d}.{p}"])
            w = b - rows * kk - 4 * rows * n - 8   # one expert's packed bytes
            ops += o
            nbytes += hit * w + rows * kk + 4 * rows * n
        total += kernels.least_s(ops, nbytes, peaks["int8_ops"],
                                 peaks["hbm_bytes_per_s"])
    return total


def decode_need_s(raw, policy, lens, peaks):
    """Least seconds of one decode launch by kernel class: the 2-D packed
    matmuls and the grouped expert matmuls at one row per live slot, and
    every layer's decode attention over the rows the live slots attend."""
    m = len(lens)
    mm = 0.0
    for i in range(raw["num_hidden_layers"]):
        for p, (k, n) in matmul_shapes(raw, i).items():
            o, b = counts.quant_matmul(m, k, n,
                                       policy["w_bits"][f"L{i:03d}.{p}"])
            mm += kernels.least_s(o, b, peaks["int8_ops"],
                                  peaks["hbm_bytes_per_s"])
    o, b = dense.decode_attn(raw, sum(lens), m)
    return {"matmul": mm,
            "expert_matmul": expert_matmul_least_s(raw, policy, m, peaks),
            "decode_attn": raw["num_hidden_layers"] * kernels.least_s(
                o, b, peaks["bf16_flops"], peaks["hbm_bytes_per_s"])}


def token_flops(raw: dict, context: int) -> float:
    """Model FLOPs of one generated token at ``context`` attended rows: every
    layer's projections (a MoE layer's router, its ``k`` routed experts and
    its shared ones), the head and attention."""
    d, E, k, _, _ = dims(raw)
    L = raw["num_hidden_layers"]
    H = raw["num_attention_heads"]
    hd = raw.get("head_dim", d // H)
    params = 0.0
    for i in range(L):
        params += sum(a * b for a, b in matmul_shapes(raw, i).values())
        if i >= raw["first_k_dense_replace"]:
            params += d * E + k * sum(a * b for a, b in
                                      expert_shapes(raw).values())
    return 2 * params + 2 * d * raw["vocab_size"] + 4 * L * H * hd * context


def decode_flops(raw: dict, lens) -> float:
    """Model FLOPs of one decode launch whose live slots attend ``lens``
    rows."""
    per_tok = token_flops(raw, 0)
    att = token_flops(raw, 1) - per_tok
    return len(lens) * per_tok + sum(lens) * att
