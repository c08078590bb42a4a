"""Serving a fine-grained MoE from packed expert stacks: the grouped
packed-expert kernel, dropless routing against a plain reference layer, the
``pallas-grouped`` dispatch route, and a tiny DeepSeekMoE through the
continuous-batching engine on the ring with its expert-load counter."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.configs.base import MoEConfig
from repro.core.policy import MPQPolicy
from repro.core.quantizer import bit_range
from repro.dist.axes import NO_AXES
from repro.kernels import quant_matmul as qmm
from repro.launch.engine import DecodeEngine, EngineConfig
from repro.launch.scheduler import Request
from repro.models import lm, moe as moe_mod
from repro.models.quant_layers import QuantContext
from repro.obs.metrics import MetricsRegistry
from repro.runtime import dispatch, packing
from repro.runtime.session import QuantizedSession, effective_weight_scale

BITS = (2, 3, 4, 5, 6)


# ===========================================================================
# the grouped kernel
# ===========================================================================
GROUPS = {
    # ragged, two experts without rows
    "ragged": [5, 0, 37, 2, 0, 9],
    # one expert takes every row
    "one-takes-all": [0, 0, 0, 53, 0, 0],
    # every expert over a tile of 32 rows, one exactly a tile
    "multi-tile": [33, 32, 70, 1, 40, 3],
}


@pytest.mark.parametrize("groups", sorted(GROUPS))
@pytest.mark.parametrize("bits", [3, 4, 5, 6])
def test_quant_matmul_grouped_matches_int8_einsum(bits, groups):
    """Interpret mode: each row sorted by expert times its expert's packed
    slab equals the plain int32 einsum of the codes times the expert's
    scale, bitwise; rows of experts without rows are never asked for. K 40
    fills no whole 8-row group of the planes."""
    gs = GROUPS[groups]
    r = np.random.default_rng(bits)
    E, M, K, N = len(gs), sum(gs), 40, 24
    qmin, qmax = bit_range(bits, True)
    q = r.integers(qmin, qmax + 1, size=(E, K, N)).astype(np.int8)
    x = r.integers(-128, 128, size=(M, K)).astype(np.int8)
    scale = r.uniform(0.5, 2.0, size=E).astype(np.float32)
    layout = packing._layout_for(bits)
    w = packing._pack(jnp.asarray(q), layout, bits)
    got = qmm.quant_matmul_grouped(
        jnp.asarray(x), w, jnp.asarray(scale), jnp.asarray(gs, jnp.int32),
        layout=layout, bits=bits, k=K, interpret=True)
    g = np.repeat(np.arange(E), gs)
    want = (np.einsum("mk,mkn->mn", x.astype(np.int32),
                      q[g].astype(np.int32)).astype(np.float32)
            * scale[g][:, None])
    np.testing.assert_array_equal(np.asarray(got), want)


def test_grouped_layout_reads_only_experts_with_rows():
    """Every used tile is one expert's and names an expert with rows;
    tiles past the used ones repeat the last used tile's expert, so the
    weight's index map names no expert without rows."""
    gs = jnp.asarray([3, 0, 40, 0, 1], jnp.int32)
    dest, tile_g, used = qmm.grouped_layout(gs, 44, 32)
    assert int(used[0]) == 1 + 2 + 1
    assert list(np.asarray(tile_g)) == [0, 2, 2, 4] + [4] * 3
    # expert 0's rows at tile 0, expert 2's from tile 1, expert 4's at 3
    np.testing.assert_array_equal(
        np.asarray(dest), [0, 1, 2] + list(range(32, 72)) + [96])


# ===========================================================================
# dropless routing against a plain reference layer
# ===========================================================================
E, K_TOP, D, F = 8, 2, 32, 16


def _moe_params(seed, skewed):
    moe = MoEConfig(n_experts=E, top_k=K_TOP, n_shared=1, d_ff=F)
    p = moe_mod.moe_init(jax.random.PRNGKey(seed), D, moe, BITS, True)
    # distinct step sizes per expert
    for name in ("wi", "wg", "wo"):
        f = jnp.linspace(0.7, 1.4, E)[:, None]
        p[name]["s_w"] = p[name]["s_w"] * f
        p[name]["s_a"] = p[name]["s_a"] * f[::-1]
    if skewed:
        # every token's largest logit is expert 3's
        w = np.zeros((D, E), np.float32)
        w[0, 3], w[1, 5] = 8.0, 2.0
        p["router"]["w"] = jnp.asarray(w)
    return p


def _tokens(seed, T):
    r = np.random.default_rng(seed)
    x = r.normal(size=(T, D)).astype(np.float32)
    x[:, 0] = np.abs(x[:, 0]) + 1.0     # the skewed router's direction
    return jnp.asarray(x)


def _fq(v, s, bits):
    lo, hi = bit_range(bits, True)
    s = jnp.maximum(s, 1e-9)
    return jnp.round(jnp.clip(v / s, lo, hi)) * s


def reference_moe(x, p, w_idx, a_idx, norm_topk):
    """Every token through every expert, each weighted by its top-k router
    weight or zero; then the shared MLP. Plain float32 jax.numpy."""
    wb, ab = BITS[w_idx], BITS[a_idx]
    probs = jax.nn.softmax(x @ p["router"]["w"], axis=-1)
    top_w, top_i = jax.lax.top_k(probs, K_TOP)
    if norm_topk:
        top_w = top_w / top_w.sum(-1, keepdims=True)
    gate = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], top_i].set(top_w)

    def proj(h, q, e=None):
        s_w, s_a, w = q["s_w"][..., w_idx], q["s_a"][..., a_idx], q["w"]
        if e is not None:
            s_w, s_a, w = s_w[e], s_a[e], w[e]
        return _fq(h, s_a, ab) @ _fq(w, s_w, wb)

    out = jnp.zeros_like(x)
    for e in range(E):
        h = jax.nn.silu(proj(x, p["wg"], e)) * proj(x, p["wi"], e)
        out = out + gate[:, e:e + 1] * proj(h, p["wo"], e)
    hs = jax.nn.silu(proj(x, p["shared_wg"])) * proj(x, p["shared_wi"])
    return out + proj(hs, p["shared_wo"])


def _ctx():
    return QuantContext.make(BITS, True, compute_dtype=jnp.float32)


def _moe_bits(w_idx, a_idx):
    names = ("wi", "wg", "wo", "shared_wi", "shared_wg", "shared_wo")
    return {n: {"w": w_idx, "a": a_idx} for n in names}


# float32 summation order alone separates the program from the reference
# (the same codes on both sides): 1e-4 of the output's range
TOL = 1e-4


def _rel_err(got, want):
    got, want = np.asarray(got), np.asarray(want)
    return float(np.abs(got - want).max() / np.abs(want).max())


@pytest.mark.parametrize("norm_topk", [True, False])
@pytest.mark.parametrize("router", ["random", "skewed"])
def test_dropless_moe_matches_reference(norm_topk, router):
    """The serving ``moe_ffn`` computes every (token, expert) pair the
    router picks: 512 tokens, E 8, top 2, one shared expert, at 3-bit
    weights and 5-bit activations, each expert with its own step sizes.
    The skewed router sends every token to one expert, twice the 256 rows
    capacity dispatch would keep for it."""
    p = _moe_params(1, router == "skewed")
    x = _tokens(2, 512)
    moe = MoEConfig(n_experts=E, top_k=K_TOP, n_shared=1, d_ff=F,
                    norm_topk=norm_topk)
    got, _ = moe_mod.moe_ffn(x.reshape(2, 256, D), p, moe, _moe_bits(1, 3),
                             _ctx(), "silu", True, NO_AXES, dropless=True)
    want = reference_moe(x, p, 1, 3, norm_topk)
    assert _rel_err(got.reshape(512, D), want) < TOL


def test_capacity_dispatch_drops_the_skewed_case():
    """The same skewed case under the training path's capacity dispatch
    drops tokens and misses the reference by far more than the tolerance:
    the check above tells dropless routing from capacity routing."""
    p = _moe_params(1, True)
    x = _tokens(2, 512)
    moe = MoEConfig(n_experts=E, top_k=K_TOP, n_shared=1, d_ff=F)
    assert moe_mod.capacity(512, moe) == 256
    got, _ = moe_mod.moe_ffn(x.reshape(2, 256, D), p, moe, _moe_bits(1, 3),
                             _ctx(), "silu", True, NO_AXES, dropless=False)
    want = reference_moe(x, p, 1, 3, True)
    assert _rel_err(got.reshape(512, D), want) > 100 * TOL


def _packed(p, w_idx, a_idx):
    """The MoE params packed as a session packs them."""
    out = {"router": p["router"]}
    for name, q in p.items():
        if name == "router":
            continue
        wb = BITS[w_idx]
        s_w = effective_weight_scale(q["s_w"], w_idx, q["w"].size, wb,
                                     w_ndim=q["w"].ndim)
        out[name] = packing.pack_linear(q["w"], wb, s_w, BITS[a_idx],
                                        q["s_a"][..., a_idx])
    return out


@pytest.mark.parametrize("w_idx", [0, 1, 2, 3])
def test_dropless_packed_routes(w_idx):
    """Packed expert stacks: ``dequant-fp`` is the fake-quant graph bitwise
    (the CPU route); the grouped kernel (interpreted) agrees with it to
    rounding, at 2-bit (``quad2`` codes unpacked to int8 first), 3-, 4- and
    5-bit stacks; the route counter names it."""
    p = _moe_params(3, False)
    x = _tokens(4, 96).reshape(1, 96, D)
    moe = MoEConfig(n_experts=E, top_k=K_TOP, n_shared=1, d_ff=F,
                    norm_topk=False)
    ctx = _ctx()
    fake, _ = moe_mod.moe_ffn(x, p, moe, _moe_bits(w_idx, 3), ctx, "silu",
                              True, NO_AXES, dropless=True)
    pk = _packed(p, w_idx, 3)
    assert dispatch.kernel_eligible(dispatch.GROUPED_EQN, pk["wi"]) == \
        "pallas-grouped"
    fp, _ = moe_mod.moe_ffn(x, pk, moe, None, ctx, "silu", True, NO_AXES,
                            dropless=True)
    assert bool(jnp.all(fp == fake))
    reg = MetricsRegistry()
    with dispatch.force_route("matmul", "pallas-grouped"), \
            dispatch.metrics_scope(reg):
        kern, _ = moe_mod.moe_ffn(x, pk, moe, None, ctx, "silu", True,
                                  NO_AXES, dropless=True)
    assert reg.value("dispatch.route.pallas-grouped") == 3
    assert _rel_err(kern, fp) < TOL


# ===========================================================================
# routes of 2-D weights
# ===========================================================================
def _parent_kernel_eligible(eqn, pl):
    """The 2-D rule before expert stacks had a route."""
    if len(pl.shape) != 2 or not dispatch._kernel_form(eqn):
        return None
    if pl.per_channel:
        return None
    if not pl.a_signed and pl.a_bits > 7:
        return None
    if (pl.layout == "nib4" and pl.shape[-2] % 2 == 0
            and not pl.sharded_layout()):
        return "pallas-w4"
    if (pl.layout == "planes" and not pl.sharded_layout()
            and qmm.planes_tiled(*pl.shape)):
        return "pallas-planes"
    if pl.w_bits <= 8:
        return "pallas-int8"
    return None


@pytest.mark.parametrize("w_bits", [2, 3, 4, 5, 6, 8])
def test_2d_weight_routes_unchanged(w_bits):
    """Every 2-D weight takes the route it took before expert stacks had
    one: over shapes that tile and do not, odd contraction dims, both
    orientations, per-channel scales, unsigned 8-bit activations and
    tensor-parallel packing."""
    eqns = ("bsd,de->bse", "bse,ed->bsd", "td,df->tf",
            dispatch.GROUPED_EQN)
    seen = set()
    for (k, n) in ((1024, 3072), (3072, 1024), (2048, 1408), (13, 9),
                   (1408, 2048), (256, 128)):
        for per_channel in (False, True):
            for a_signed, a_bits in ((True, 8), (False, 8), (False, 4)):
                for shards in (1, 2):
                    pl = packing.PackedLinear(
                        codes=np.zeros((1, 1), np.uint8),
                        scale=np.zeros((n,), np.float32),
                        s_a=np.zeros((), np.float32), w_bits=w_bits,
                        a_bits=a_bits, a_signed=a_signed,
                        layout=packing._layout_for(w_bits), shape=(k, n),
                        per_channel=per_channel,
                        shard_dim=0 if shards > 1 else None,
                        shard_count=shards)
                    for eqn in eqns:
                        want = _parent_kernel_eligible(eqn, pl)
                        assert dispatch.kernel_eligible(eqn, pl) == want
                        seen.add(want)
                        with dispatch.force_route("matmul", None):
                            assert dispatch.resolve(eqn, pl, "tpu") == (
                                want or "dequant-fp")
    assert "pallas-grouped" not in seen


# ===========================================================================
# a tiny DeepSeekMoE through the engine
# ===========================================================================
@pytest.fixture(scope="module")
def tiny_deepseek():
    cfg = smoke_config("deepseek-moe-16b")
    assert cfg.moe.norm_topk is False and cfg.moe.first_dense_layers == 1
    params = lm.init_params(jax.random.PRNGKey(5), cfg)
    ql = lm.enumerate_qlayers(cfg)
    bits = sorted(int(b) for b in cfg.bits)
    policy = MPQPolicy(
        {q.name: bits[(i + 1) % 5] for i, q in enumerate(ql)},
        {q.name: bits[(i + 3) % 5] for i, q in enumerate(ql)})
    ctx = QuantContext.make(cfg.bits, cfg.quant_act_signed,
                            compute_dtype=jnp.float32)
    return cfg, params, policy, ql, ctx


# The packed session on the CPU takes dequant-fp, which is the fake-quant
# graph op for op, and with no token dropped at this size dropless and
# capacity routing sum the same products: float32 rounding is all that may
# separate the engine's logits from the full forward (read: 0).
ENGINE_TOL = 1e-5


def _served(eng, reqs):
    eng.submit_all([Request(rid=q.rid, tokens=q.tokens, max_new=q.max_new)
                    for q in reqs])
    return eng.run()


def _requests(cfg):
    r = np.random.default_rng(7)
    return [Request(rid=i, tokens=r.integers(0, cfg.vocab, size=n)
                    .astype(np.int32), max_new=g)
            for i, (n, g) in enumerate([(9, 6), (5, 8), (12, 4), (7, 5)])]


def test_tiny_deepseek_engine_matches_full_forward(tiny_deepseek):
    """Packed session on the ring, bucketed prefill then decode (float KV
    rows, so nothing but the program's own path is compared): the logits
    every served token was picked from match the fake-quant full forward
    over the served sequence at each position. The expert-load counter
    adds up: every launch routes top_k rows a slot in each MoE layer."""
    cfg, params, policy, _, ctx = tiny_deepseek
    sess = QuantizedSession(cfg, params, policy, ctx, mode="packed",
                            kv_quant="none")
    slots = 3
    eng = DecodeEngine(sess.params, cfg, None, ctx, NO_AXES,
                       EngineConfig(slots=slots, cache_len=32,
                                    kv_quant="none", kv_layout="ring",
                                    record_logits=True),
                       adapter=sess)
    reqs = _requests(cfg)
    out = _served(eng, reqs)
    bits = lm.bits_from_policy(cfg, policy, lm.enumerate_qlayers(cfg))
    worst = 0.0
    for req in reqs:
        c = out[req.rid]
        seq = np.concatenate([req.tokens, c.tokens[:-1]])[None]
        full, _ = lm.apply_train(params, cfg, {"tokens": jnp.asarray(seq)},
                                 bits, ctx, NO_AXES, remat=False)
        want = np.asarray(full[0, len(req.tokens) - 1:])
        got = np.asarray(c.logits)
        scale = want.max() - want.min()
        worst = max(worst, float(np.abs(got - want).max() / scale))
    assert worst <= ENGINE_TOL, worst

    load = eng.expert_load()
    n_moe = cfg.n_layers - cfg.moe.first_dense_layers
    assert load["tokens"].shape == (n_moe, cfg.moe.n_experts)
    launches = int(eng.metrics.value("engine.decode_steps"))
    assert load["launches"] == launches > 0
    np.testing.assert_array_equal(load["tokens"].sum(-1),
                                  launches * slots * cfg.moe.top_k)
    assert 0 < load["experts_hit"] <= min(cfg.moe.n_experts,
                                          slots * cfg.moe.top_k)
    assert eng.metrics.value("engine.moe.experts_hit") == \
        load["experts_hit"]


def test_tiny_deepseek_int8_ring_matches_fake_quant_engine(tiny_deepseek):
    """With int8 KV on the ring, the packed engine serves the tokens and
    logits of the reference engine on the fake-quant params whose KV rows
    are quantize-dequantized (``kv_quant="fake"``), bitwise. Against the
    float-KV full forward the int8 rows move this tiny model's logits by
    0.05 to 0.13 of their range, as 2- to 6-bit activation codes flip: that
    comparison is the one above, on float KV."""
    cfg, params, policy, ql, ctx = tiny_deepseek
    sess = QuantizedSession(cfg, params, policy, ctx, mode="packed",
                            kv_quant="int8")
    ecfg = dict(slots=3, cache_len=32, kv_layout="ring", record_logits=True)
    eng = DecodeEngine(sess.params, cfg, None, ctx, NO_AXES,
                       EngineConfig(kv_quant="int8", **ecfg), adapter=sess)
    ref = DecodeEngine(params, cfg, lm.bits_from_policy(cfg, policy, ql),
                       ctx, NO_AXES, EngineConfig(kv_quant="fake", **ecfg))
    reqs = _requests(cfg)
    got, want = _served(eng, reqs), _served(ref, reqs)
    for req in reqs:
        assert got[req.rid].tokens == want[req.rid].tokens
        np.testing.assert_array_equal(np.asarray(got[req.rid].logits),
                                      np.asarray(want[req.rid].logits))


def test_expert_load_counter_stays_on_the_device(tiny_deepseek):
    """The counter rides in the decode state through every launch, admission
    and eviction; only ``expert_load`` brings it to the host, and a state
    without MoE layers has none."""
    cfg, params, policy, _, ctx = tiny_deepseek
    sess = QuantizedSession(cfg, params, policy, ctx, mode="packed",
                            kv_quant="int8")
    eng = DecodeEngine(sess.params, cfg, None, ctx, NO_AXES,
                       EngineConfig(slots=2, cache_len=16, kv_quant="int8"),
                       adapter=sess)
    assert set(eng.state) == {"sites", "moe"}
    assert isinstance(eng.state["moe"]["tokens"], jax.Array)
    assert eng.expert_load()["launches"] == 0
    eng.submit_all([Request(rid=0, tokens=np.arange(5, dtype=np.int32),
                            max_new=3)])
    eng.run()
    assert set(eng.state) == {"sites", "moe"}
    assert eng.expert_load()["launches"] == 2

    dense = smoke_config("qwen3-0.6b")
    dparams = lm.init_params(jax.random.PRNGKey(0), dense)
    dsess = QuantizedSession(
        dense, dparams, MPQPolicy.uniform(lm.enumerate_qlayers(dense), 4),
        ctx, kv_quant="int8")
    deng = DecodeEngine(dsess.params, dense, None, ctx, NO_AXES,
                        EngineConfig(slots=2, cache_len=16,
                                     kv_quant="int8"), adapter=dsess)
    assert "moe" not in deng.state and deng.expert_load() is None
