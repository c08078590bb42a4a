"""Continuous-batching engine: token-identity with the fixed-batch path,
strictly-fewer decode steps on staggered schedules, and the slot
admission/eviction invariants (no leaks, no KV mixing) under random
arrival/finish schedules (hypothesis)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import smoke_config
from repro.dist.axes import NO_AXES
from repro.launch.engine import DecodeEngine, EngineConfig
from repro.launch.scheduler import Request, Scheduler
from repro.models import attention as attn
from repro.models import lm
from repro.models.quant_layers import QuantContext

CACHE_LEN = 16
SLOTS = 2


@pytest.fixture(scope="module")
def setup():
    cfg = smoke_config("qwen3-0.6b")
    rng = jax.random.PRNGKey(0)
    params = lm.init_params(rng, cfg)
    ctx = QuantContext.make(cfg.bits, cfg.quant_act_signed,
                            compute_dtype=jnp.float32)
    bits = lm.bits_uniform(cfg, 3)
    # the pre-engine serving path: per-request prefill + shared-position
    # decode — the token-for-token oracle the engine must match
    prefill = jax.jit(lambda p, b: lm.apply_prefill(
        p, cfg, b, bits, ctx, NO_AXES, prefill_cap=CACHE_LEN))
    decode = jax.jit(lambda p, t, pos, s: lm.apply_decode(
        p, cfg, t, pos, s, bits, ctx, NO_AXES))
    eng = DecodeEngine(params, cfg, bits, ctx, NO_AXES,
                       EngineConfig(slots=SLOTS, cache_len=CACHE_LEN))
    return dict(cfg=cfg, params=params, ctx=ctx, bits=bits,
                prefill=prefill, decode=decode, eng=eng)


def oracle(setup, req):
    """Fixed-path greedy decode of one request (shared scalar positions)."""
    lg, st = setup["prefill"](setup["params"],
                              {"tokens": jnp.asarray(req.tokens)[None]})
    toks = [int(jnp.argmax(lg[0]))]
    while len(toks) < req.max_new:
        pos = jnp.asarray(req.prompt_len + len(toks) - 1, jnp.int32)
        lg, st = setup["decode"](setup["params"],
                                 jnp.asarray([[toks[-1]]], jnp.int32), pos, st)
        toks.append(int(jnp.argmax(lg[0])))
    return toks


def make_requests(specs):
    """specs: [(prompt_len, max_new, arrival_gap)] -> staggered Requests."""
    data_rng = np.random.default_rng(7)
    reqs, arrival = [], 0
    for i, (p, g, gap) in enumerate(specs):
        arrival += gap
        toks = data_rng.integers(0, 500, size=p).astype(np.int32)
        reqs.append(Request(rid=i, tokens=toks, max_new=g, arrival=arrival))
    return reqs


def run_engine(setup, reqs, policy):
    eng = setup["eng"]
    eng.reset(policy)
    eng.submit_all(reqs)
    out = eng.run()
    return eng, out


def cache_pos_leaves(state):
    leaves = jax.tree.flatten(
        state, is_leaf=lambda x: isinstance(x, attn.KVCache))[0]
    return [np.asarray(c.pos) for c in leaves if isinstance(c, attn.KVCache)]


# ---------------------------------------------------------------------------
def test_token_identical_and_fewer_steps_on_stagger(setup):
    specs = [(8, 6, 0), (4, 2, 0), (6, 3, 1), (4, 6, 2), (8, 2, 2)]
    reqs = make_requests(specs)
    cont, cont_out = run_engine(setup, reqs, "continuous")
    cont_stats = cont.stats
    fixed, fixed_out = run_engine(setup, reqs, "fixed")

    for r in reqs:
        want = oracle(setup, r)
        assert cont_out[r.rid].tokens == want, f"continuous != oracle rid {r.rid}"
        assert fixed_out[r.rid].tokens == want, f"fixed != oracle rid {r.rid}"
    # mixed arrivals + staggered lengths: continuous batching must finish in
    # strictly fewer decode steps than padding every round to its max
    assert cont_stats.decode_steps < fixed.stats.decode_steps
    assert cont_stats.slot_steps <= fixed.stats.padded_slot_steps


def test_sjf_policy_matches_tokens(setup):
    reqs = make_requests([(8, 3, 0), (4, 3, 0), (6, 2, 0)])
    _, sjf_out = run_engine(setup, reqs, "continuous-sjf")
    for r in reqs:
        assert sjf_out[r.rid].tokens == oracle(setup, r)


@settings(max_examples=4)
@given(st.lists(st.tuples(st.sampled_from([4, 6, 8]),   # prompt length
                          st.integers(1, 4),            # max_new
                          st.integers(0, 3)),           # arrival gap
                min_size=1, max_size=6))
def test_random_schedule_never_leaks(setup, specs):
    """Property: a random arrival/finish schedule never leaks slots, never
    mixes KV rows between sequences, and matches the fixed path
    token-for-token."""
    reqs = make_requests(specs)
    eng, out = run_engine(setup, reqs, "continuous")
    # every request completed with exactly its budget, no slot left occupied
    assert sorted(out) == [r.rid for r in reqs]
    assert all(s is None for s in eng.slots)
    assert all(len(out[r.rid].tokens) == r.max_new for r in reqs)
    # eviction invariant: after drain every cache row is fully invalidated —
    # a reused slot can only ever attend to entries its own prefill wrote
    for pos in cache_pos_leaves(eng.state):
        assert (pos == -1).all()
    # no KV mixing: any cross-slot leakage corrupts the greedy argmax chain
    for r in reqs:
        assert out[r.rid].tokens == oracle(setup, r), f"rid {r.rid}"


def test_scheduler_units():
    sched = Scheduler("fixed")
    sched.submit(Request(0, np.zeros(4, np.int32), 2))
    sched.submit(Request(1, np.zeros(4, np.int32), 2))
    assert sched.admit(0, [1], occupied=1) == []          # waits for empty
    picks = sched.admit(0, [0, 1], occupied=0)
    assert [s for _, s in picks] == [0, 1] and not sched.pending

    sched = Scheduler("continuous", prefill_chunk=4)
    sched.submit(Request(0, np.zeros(10, np.int32), 2))
    assert sched.admit(0, [0], occupied=0) == []          # credit 4 < 10
    assert sched.admit(1, [0], occupied=0) == []          # credit 8 < 10
    picks = sched.admit(2, [0], occupied=0)               # credit 12 >= 10
    assert [r.rid for r, _ in picks] == [0]

    sched = Scheduler("continuous-sjf", prefill_chunk=100)
    sched.submit(Request(0, np.zeros(8, np.int32), 1))
    sched.submit(Request(1, np.zeros(2, np.int32), 1))
    picks = sched.admit(0, [0, 1], occupied=0)
    assert [r.rid for r, _ in picks] == [1, 0]            # shortest first

    sched = Scheduler("continuous", prefill_chunk=8)
    sched.submit(Request(0, np.zeros(4, np.int32), 1, arrival=5))
    assert sched.admit(0, [0], occupied=0) == []          # not arrived yet
    assert [r.rid for r, _ in sched.admit(5, [0], occupied=0)] == [0]


def test_fixed_round_all_done_at_admission(setup):
    """Regression: a fixed-policy round whose every request finishes at
    admission (max_new=1 -> the prefill token is the whole generation) must
    release its held slots instead of tripping the drain-time leak check."""
    reqs = make_requests([(4, 1, 0), (4, 1, 0), (6, 1, 0)])
    eng, out = run_engine(setup, reqs, "fixed")
    assert sorted(out) == [0, 1, 2]
    assert all(len(out[r.rid].tokens) == 1 for r in reqs)
    assert all(s is None for s in eng.slots)
    for r in reqs:
        assert out[r.rid].tokens == oracle(setup, r)


def test_scheduler_credit_resets_between_waves():
    sched = Scheduler("continuous", prefill_chunk=4)
    sched.submit(Request(0, np.zeros(4, np.int32), 1))
    assert [r.rid for r, _ in sched.admit(0, [0], occupied=0)] == [0]
    # queue drained with banked credit; a fresh wave must start from zero
    sched.submit(Request(1, np.zeros(10, np.int32), 1))
    assert sched.admit(1, [0], occupied=0) == []       # credit 4 < 10 again
    assert sched.admit(2, [0], occupied=0) == []
    assert [r.rid for r, _ in sched.admit(3, [0], occupied=0)] == [1]


def test_engine_rejects_oversized_request(setup):
    eng = setup["eng"]
    eng.reset("continuous")
    with pytest.raises(ValueError, match="cache_len"):
        eng.submit(Request(0, np.zeros(12, np.int32), 8))  # 20 > 16


def test_engine_rejects_duplicate_rid(setup):
    eng = setup["eng"]
    eng.reset("continuous")
    eng.submit(Request(0, np.zeros(4, np.int32), 2))
    with pytest.raises(ValueError, match="already"):
        eng.submit(Request(0, np.zeros(4, np.int32), 2))


# ---------------------------------------------------------------------------
# paged KV layout: shared-prefix serving stays token-identical to ring
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def packed_setup():
    """Packed int8 session + engine factory — the only adapter the paged
    layout serves (it needs the chunked ``append`` path)."""
    from repro.core.policy import MPQPolicy
    from repro.runtime.session import QuantizedSession

    cfg = smoke_config("limpq-demo")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    ctx = QuantContext.make(cfg.bits, cfg.quant_act_signed,
                            compute_dtype=jnp.float32)
    policy = MPQPolicy.uniform(lm.enumerate_qlayers(cfg), 4)

    def build(layout, cache_len=29, **ecfg):
        sess = QuantizedSession(cfg, params, policy, ctx, mode="packed",
                                kv_quant="int8")
        eng = DecodeEngine(sess.params, cfg, None, ctx, NO_AXES,
                           EngineConfig(slots=2, cache_len=cache_len,
                                        kv_quant="int8", kv_layout=layout,
                                        page_size=8, **ecfg), adapter=sess)
        return sess, eng

    return dict(cfg=cfg, params=params, ctx=ctx, build=build)


def test_paged_engine_token_identical_and_saves_prefill(packed_setup):
    """Three requests share a 16-token (2-page) prompt prefix, one doesn't;
    the paged engine must generate exactly the ring engine's tokens while
    re-mapping the shared pages instead of re-prefilling them — >0 FLOPs
    saved, strictly fewer prefill tokens, and ONE prefill compile shape
    (chunked append replaces the ring path's prompt-length bucketing)."""
    rng = np.random.default_rng(11)
    shared = rng.integers(1, 400, size=16)

    def mk(rid, tail, arrival=0):
        toks = np.concatenate(
            [shared, rng.integers(1, 400, size=tail)]).astype(np.int32)
        return Request(rid=rid, tokens=toks, max_new=4, arrival=arrival)

    reqs = [mk(0, 5), mk(1, 3, 1), mk(2, 7, 2),
            Request(rid=3, tokens=rng.integers(1, 400, size=9).astype(
                np.int32), max_new=4, arrival=2)]
    toks, stats, logits = {}, {}, {}
    from repro.runtime import dispatch
    for layout in ("ring", "paged"):
        _, eng = packed_setup["build"](layout, record_logits=True)
        with dispatch.force_decode_attn("dequant-fp"):
            eng.submit_all(reqs)
            out = eng.run()
        toks[layout] = {r.rid: out[r.rid].tokens for r in reqs}
        logits[layout] = {r.rid: out[r.rid].logits for r in reqs}
        stats[layout] = eng.stats
        if layout == "paged":
            eng.pool.check()            # no page leaked after the drain
            assert all(s is None for s in eng.slots)
    assert toks["paged"] == toks["ring"]
    for r in reqs:   # the logits each token was taken from, bit for bit
        got = logits["paged"][r.rid]
        assert got.shape[0] == len(toks["paged"][r.rid])
        assert list(got.argmax(-1)) == toks["paged"][r.rid]
        np.testing.assert_array_equal(got, logits["ring"][r.rid])
    assert stats["paged"].prefill_flops_saved > 0
    assert stats["ring"].prefill_flops_saved == 0
    assert stats["paged"].prefill_tokens < stats["ring"].prefill_tokens
    assert stats["paged"].prefill_compiles == 1
    assert stats["paged"].kv_unique_pages > 0


def test_paged_engine_validation(packed_setup):
    """The paged layout's construction-time contract: route-registry
    validation plus int8-KV and append-capable-adapter requirements."""
    cfg, params, ctx = (packed_setup[k] for k in ("cfg", "params", "ctx"))
    bits = lm.bits_uniform(cfg, 3)
    with pytest.raises(ValueError, match="kv_layout"):
        DecodeEngine(params, cfg, bits, ctx, NO_AXES,
                     EngineConfig(slots=2, cache_len=16,
                                  kv_layout="blocked"))
    # the fake-quant reference adapter has no chunked append path
    with pytest.raises(ValueError, match="append-capable"):
        DecodeEngine(params, cfg, bits, ctx, NO_AXES,
                     EngineConfig(slots=2, cache_len=16, kv_quant="int8",
                                  kv_layout="paged"))
    with pytest.raises(ValueError, match="int8"):
        DecodeEngine(params, cfg, bits, ctx, NO_AXES,
                     EngineConfig(slots=2, cache_len=16, kv_quant="none",
                                  kv_layout="paged"))


def test_serve_config_validates_routes():
    """``ServeConfig`` rejects bad combinations at construction — before
    any engine or session is built."""
    from repro.launch.serve import ServeConfig

    scfg = ServeConfig(kv_layout="paged", page_size=8)
    assert scfg.engine_config().kv_layout == "paged"
    # a non-int8 engine of the same run silently serves through ring
    assert scfg.engine_config(kv_quant="fake").kv_layout == "ring"
    with pytest.raises(ValueError, match="paged"):
        ServeConfig(kv_layout="paged", kv="fp")
    with pytest.raises(ValueError, match="kv_layout"):
        ServeConfig(kv_layout="blocked")
    with pytest.raises(ValueError, match="decode_attn"):
        ServeConfig(decode_attn="flash")
    with pytest.raises(ValueError, match="schedule"):
        ServeConfig(schedule="round-robin")
    with pytest.raises(ValueError, match="single-device"):
        ServeConfig(kv_layout="paged", mesh="2x4")


def test_roofline_scheduler_hook():
    from repro.configs import get_config
    from repro.dist import roofline

    cfg = get_config("qwen3-0.6b")
    cost = roofline.decode_step_cost(cfg, 8, cache_tokens=2048, tp_size=4)
    assert cost["compute_s"] > 0 and cost["memory_s"] > 0
    assert cost["collective_s"] > 0            # tp>1 moves activation bytes
    assert cost["step_s"] == max(cost["compute_s"], cost["memory_s"],
                                 cost["collective_s"])
    assert cost["dominant"] == "memory"        # decode re-reads every weight

    chunk = roofline.suggest_prefill_chunk(cfg, 8, cache_tokens=2048)
    assert 16 <= chunk <= 512
    # more HBM bandwidth -> smaller memory ceiling -> less free headroom
    fast_hbm = roofline.ChipSpec(name="x", hbm_bytes_s=8 * 819e9)
    assert roofline.suggest_prefill_chunk(
        cfg, 8, cache_tokens=2048, chip=fast_hbm) <= chunk


def test_chip_envelope_by_device_kind(monkeypatch):
    """Off-TPU the planners model the v5e table entry; on a TPU the
    envelope is looked up by device kind and an unknown kind raises."""
    from repro.dist import roofline

    assert roofline.local_chip() is roofline.DEFAULT_CHIP
    assert roofline.CHIPS["TPU v5 lite"] is roofline.DEFAULT_CHIP
    assert roofline.DEFAULT_CHIP.ici_bytes_s == 1600e9 / 8

    class _Dev:
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
    assert roofline.local_chip() is roofline.CHIPS["TPU v5 lite"]
    _Dev.device_kind = "TPU v9 imaginary"
    with pytest.raises(ValueError, match="no ChipSpec"):
        roofline.local_chip()


def test_flash_fwd_route_is_counted_and_resolved_from_shape():
    """The flash forward's fallback to the jnp scan is a counted route:
    Pallas only on a TPU, for a sequence that tiles into kv blocks, and
    outside a multi-device mesh."""
    from repro.dist.axes import MeshAxes
    from repro.obs.metrics import MetricsRegistry
    from repro.runtime import dispatch

    class _Mesh:
        size = 4

    reg = MetricsRegistry()
    with dispatch.metrics_scope(reg):
        assert dispatch.resolve_flash_fwd(2048, 512, backend="tpu") == "pallas"
        assert dispatch.resolve_flash_fwd(2000, 512,
                                          backend="tpu") == "jnp-scan"
        assert dispatch.resolve_flash_fwd(2048, 512,
                                          backend="cpu") == "jnp-scan"
        with dispatch.axes_scope(MeshAxes(mesh=_Mesh())):
            assert dispatch.resolve_flash_fwd(2048, 512,
                                              backend="tpu") == "jnp-scan"
            assert dispatch.resolve_decode_attn("tpu") == "dequant-fp"
        with dispatch.force_route("flash_fwd", "jnp-scan"):
            assert dispatch.resolve_flash_fwd(2048, 512,
                                              backend="tpu") == "jnp-scan"
    assert reg.value("dispatch.flash_fwd.pallas") == 1
    assert reg.value("dispatch.flash_fwd.jnp-scan") == 4
