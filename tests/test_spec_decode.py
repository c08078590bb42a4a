"""Self-speculative decoding: the low-bit draft proposes, the searched
policy verifies.  Gates the bitwise KV contract (a verify step and any
rejection-pattern rollback reproduce sequential decode's cache exactly),
engine token identity against non-speculative decode on both KV layouts
and both spec launch paths, the construction-time guards, the roofline
round model, and trace/stats reconciliation of the spec counters."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import smoke_config
from repro.core.policy import MPQPolicy
from repro.dist import roofline
from repro.dist.axes import NO_AXES
from repro.launch.engine import DecodeEngine, EngineConfig
from repro.launch.scheduler import Request
from repro.models import lm
from repro.models.quant_layers import QuantContext
from repro.runtime import dispatch
from repro.runtime import kv_cache as qkv
from repro.runtime.session import QuantizedSession, SpecSession


@pytest.fixture(scope="module")
def setup():
    cfg = smoke_config("limpq-demo")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    ctx = QuantContext.make(cfg.bits, cfg.quant_act_signed,
                            compute_dtype=jnp.float32)
    ql = lm.enumerate_qlayers(cfg)
    # mixed searched target (alternating 4/6-bit weights, 4-bit acts): the
    # draft must repack THESE weights, not a uniform toy
    policy = MPQPolicy({q.name: (4 if i % 2 else 6) for i, q in enumerate(ql)},
                       {q.name: 4 for q in ql})
    sess = SpecSession(cfg, params, policy, ctx, draft_w_bits=2,
                       kv_quant="int8")
    return dict(cfg=cfg, params=params, ctx=ctx, policy=policy, sess=sess,
                qlayers=ql)


def _caches(state):
    out = []

    def rec(x):
        if isinstance(x, qkv.CACHE_TYPES):
            out.append(x)
        return x

    jax.tree.map(rec, state,
                 is_leaf=lambda x: isinstance(x, qkv.CACHE_TYPES))
    return out


def _assert_kv_bitwise(sa, sb, what=""):
    """Bitwise cache equality: pos stamps exactly, codes + write-time
    scales on every live (pos >= 0) row.  Paged caches compare through
    the dense per-slot gather so a permuted physical page-id assignment
    (rollback returns tail pages to the free list) cannot mask or fake a
    logical difference."""
    ca, cb = _caches(sa), _caches(sb)
    assert len(ca) == len(cb) and ca
    for i, (a, b) in enumerate(zip(ca, cb)):
        if isinstance(a, qkv.PagedKVCache):
            a, b = a.gather(), b.gather()
        pa, pb = np.asarray(a.pos), np.asarray(b.pos)
        assert np.array_equal(pa, pb), f"{what} pos leaf {i}"
        m = pa >= 0
        for f in ("k", "v", "k_scale", "v_scale"):
            assert np.array_equal(np.asarray(getattr(a, f))[m],
                                  np.asarray(getattr(b, f))[m]), \
                f"{what} {f} leaf {i}"


def _sequential_reference(sess, toks, pos, states0, cuts):
    """Non-speculative reference: decode one token at a time, freezing each
    slot's state once it has consumed ``cuts[i]`` tokens — the cache a
    plain engine holds after decoding exactly the accepted prefix."""
    B, S = toks.shape
    st_ref = states0
    for j in range(S):
        _, st_next = sess.decode(sess.params, toks[:, j:j + 1], pos[:, j],
                                 st_ref)
        active = np.asarray(cuts) > j

        def sel(new, old):
            if isinstance(new, qkv.CACHE_TYPES):
                keep = jnp.asarray(active)

                def pick(arr_n, arr_o):
                    k = keep.reshape((-1,) + (1,) * (arr_n.ndim - 1))
                    return jnp.where(k, arr_n, arr_o)

                return new._replace(**{f: pick(getattr(new, f),
                                               getattr(old, f))
                                       for f in new._fields})
            return new

        st_ref = jax.tree.map(sel, st_next, st_ref,
                              is_leaf=lambda x: isinstance(x,
                                                           qkv.CACHE_TYPES))
    return st_ref


# ---------------------------------------------------------------------------
# session layer: verify == sequential decode, bitwise
# ---------------------------------------------------------------------------
def test_verify_bitwise_matches_sequential(setup):
    """One verify step over S tokens returns the same logits AND writes the
    same KV rows, bit for bit, as S one-token decode steps."""
    sess, cfg = setup["sess"], setup["cfg"]
    B, S = 2, 3
    states0 = sess.init_state(B, 16, jnp.float32, per_slot=True)
    r = np.random.default_rng(0)
    toks = jnp.asarray(r.integers(0, cfg.vocab, size=(B, S)), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    st_seq, seq_logits = states0, []
    for j in range(S):
        lj, st_seq = sess.decode(sess.params, toks[:, j:j + 1], pos[:, j],
                                 st_seq)
        seq_logits.append(np.asarray(lj))
    lv, st_ver = sess.verify(sess.params, toks, pos, states0)
    for j in range(S):
        assert np.array_equal(np.asarray(lv[:, j]), seq_logits[j]), j
    _assert_kv_bitwise(st_seq, st_ver, "verify")

    # the draft pack runs through the SAME decode adapter (one runtime,
    # two policies) and is a different function of the same weights
    ld, _ = sess.decode(sess.draft_params, toks[:, :1], pos[:, 0], states0)
    assert ld.shape == seq_logits[0].shape


@settings(max_examples=4)
@given(st.integers(0, 10_000),            # token seed
       st.sampled_from([2, 3, 4]),        # verified row count S = k + 1
       st.integers(0, 4), st.integers(0, 4))   # per-slot accepted rows
def test_rollback_any_rejection_pattern(setup, seed, S, cut0, cut1):
    """Property: after a verify step and a rollback at ANY per-slot cut —
    including cut=0 (everything rejected) and cut=S (everything accepted)
    — the cache is bitwise identical to a non-speculative session that
    decoded only the accepted tokens."""
    sess, cfg = setup["sess"], setup["cfg"]
    B = 2
    cuts = np.minimum([cut0, cut1], S).astype(np.int32)
    states0 = sess.init_state(B, 16, jnp.float32, per_slot=True)
    r = np.random.default_rng(seed)
    toks = jnp.asarray(r.integers(0, cfg.vocab, size=(B, S)), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    _, st_ver = sess.verify(sess.params, toks, pos, states0)
    rolled = lm.rollback_decode_state(st_ver, jnp.asarray(cuts))
    st_ref = _sequential_reference(sess, toks, pos, states0, cuts)
    _assert_kv_bitwise(rolled, st_ref, f"cuts={cuts.tolist()}")


# ---------------------------------------------------------------------------
# engine layer: token identity + KV identity vs a non-speculative engine
# ---------------------------------------------------------------------------
def _requests(cfg, n=3):
    rng = np.random.default_rng(7)
    shared = rng.integers(1, cfg.vocab, size=16)   # 2 full 8-token pages

    def mk(rid, tail, max_new, arrival=0):
        toks = np.concatenate(
            [shared, rng.integers(1, cfg.vocab, size=tail)]).astype(np.int32)
        return Request(rid=rid, tokens=toks, max_new=max_new,
                       arrival=arrival)

    reqs = [mk(0, 5, 6), mk(1, 3, 5, 1),
            Request(rid=2, tokens=rng.integers(
                1, cfg.vocab, size=9).astype(np.int32), max_new=4,
                arrival=2)]
    return reqs[:n]


def _engine(setup, layout, spec_k, *, slots=2, cache_len=29, trace=True):
    sess = setup["sess"]
    eng = DecodeEngine(sess.params, setup["cfg"], None, setup["ctx"],
                       NO_AXES,
                       EngineConfig(slots=slots, cache_len=cache_len,
                                    kv_quant="int8", kv_layout=layout,
                                    page_size=8, speculate=spec_k,
                                    trace=trace), adapter=sess)
    return eng


@pytest.mark.parametrize("layout", ["ring", "paged"])
def test_engine_spec_token_identical(setup, layout):
    """The speculating engine emits exactly the non-speculative engine's
    greedy tokens (paged: on COW-shared prefix pages), books per-request
    acceptance into Completions, and its trace reconciles against the
    spec counters."""
    reqs = _requests(setup["cfg"])
    with dispatch.force_decode_attn("dequant-fp"):
        base = _engine(setup, layout, 0)
        base.submit_all(reqs)
        base_out = base.run()
        spec = _engine(setup, layout, 3)
        spec.submit_all(reqs)
        spec_out = spec.run()

    for r in reqs:
        assert spec_out[r.rid].tokens == base_out[r.rid].tokens, r.rid
    s = spec.stats
    assert s.spec_rounds > 0 and s.spec_draft_tokens > 0
    assert 0.0 <= s.spec_accept_rate <= 1.0
    assert s.spec_accepted_tokens <= s.spec_draft_tokens
    # aggregate counters are exactly the per-request attribution
    assert sum(c.spec_drafted for c in spec_out.values()) \
        == s.spec_draft_tokens
    assert sum(c.spec_accepted for c in spec_out.values()) \
        == s.spec_accepted_tokens
    assert all(c.spec_drafted == c.spec_accepted == 0
               for c in base_out.values())
    # drain invariant survives rollback: no slot can attend any row — the
    # ring wipes pos stamps; paged unmaps every table entry (pages still
    # registered in the prefix registry keep their stamps for LRU reuse)
    for c in _caches(spec.state):
        if isinstance(c, qkv.PagedKVCache):
            assert (np.asarray(c.page_table) == -1).all()
        else:
            assert (np.asarray(c.pos) == -1).all()
    # trace <-> stats: one spec_verify instant per round, token sums match
    from repro.obs import trace as obs_trace
    problems = obs_trace.reconcile(spec.trace, s.as_dict())
    assert problems == [], problems
    verifies = [e for e in spec.trace.events if e.name == "spec_verify"]
    assert len(verifies) == s.spec_rounds
    if layout == "paged":
        spec.pool.check()                 # rollback leaked no pages
        assert s.prefill_flops_saved > 0  # COW prefix reuse still fired


def test_engine_spec_fused_launch_identical(setup):
    """trace=False takes the single fused draft+verify launch (the path the
    bench times); it must stay token-identical to the traced 2-launch
    path and to non-speculative decode."""
    reqs = _requests(setup["cfg"], n=2)
    with dispatch.force_decode_attn("dequant-fp"):
        base = _engine(setup, "ring", 0, trace=False)
        base.submit_all(reqs)
        base_out = base.run()
        spec = _engine(setup, "ring", 3, trace=False)
        spec.submit_all(reqs)
        spec_out = spec.run()
    assert spec.trace is None
    for r in reqs:
        assert spec_out[r.rid].tokens == base_out[r.rid].tokens, r.rid
    assert spec.stats.spec_rounds > 0


def test_engine_spec_fused_interpret_route(setup):
    """The fused-interpret decode-attention route (the kernel program the
    TPU path runs) holds the same identity on the paged layout — the
    serve-smoke CI combination."""
    reqs = _requests(setup["cfg"], n=2)
    with dispatch.force_decode_attn("fused-interpret"):
        base = _engine(setup, "paged", 0)
        base.submit_all(reqs)
        base_out = base.run()
        spec = _engine(setup, "paged", 3)
        spec.submit_all(reqs)
        spec_out = spec.run()
    for r in reqs:
        assert spec_out[r.rid].tokens == base_out[r.rid].tokens, r.rid
    assert spec.stats.spec_draft_tokens > 0


def test_engine_spec_kv_bitwise_midflight(setup):
    """Mid-flight (before eviction wipes the slot) the speculating engine's
    cache is bitwise identical to a non-speculative engine that decoded
    the same accepted tokens — draft rows past the rejection leave no
    residue.  Paged, page_size=8, prompt 13: rounds cross page
    boundaries at rows 16 and 24, so the rollback drops partial tail
    pages."""
    rng = np.random.default_rng(3)
    req = Request(rid=0, tokens=rng.integers(
        1, setup["cfg"].vocab, size=13).astype(np.int32), max_new=16)
    with dispatch.force_decode_attn("dequant-fp"):
        spec = _engine(setup, "paged", 3, slots=1, cache_len=32)
        spec.submit(req)
        for now in range(3):               # prefill + 3 spec rounds
            assert spec.step(now)
        slot = spec.slots[0]
        assert slot is not None and not slot.done
        g = len(slot.gen)
        assert g >= 4                      # >= 1 emitted token per round

        base = _engine(setup, "paged", 0, slots=1, cache_len=32)
        base.submit(req)
        now = 0
        while base.slots[0] is None or len(base.slots[0].gen) < g:
            assert base.step(now)  # admits at step 0, then 1 token/step
            now += 1
    assert base.slots[0].gen == slot.gen
    _assert_kv_bitwise(spec.state, base.state, "midflight")


# ---------------------------------------------------------------------------
# construction-time guards
# ---------------------------------------------------------------------------
def test_spec_guards(setup):
    cfg, params, ctx = setup["cfg"], setup["params"], setup["ctx"]
    # the draft grid must reuse trained indicator-bank scales: only
    # searched bit-widths exist in the bank
    with pytest.raises(ValueError, match="searched bit set"):
        SpecSession(cfg, params, setup["policy"], ctx, draft_w_bits=7,
                    kv_quant="int8")
    # a single-policy adapter has nothing to draft with
    mono = QuantizedSession(cfg, params, setup["policy"], ctx,
                            mode="packed", kv_quant="int8")
    with pytest.raises(ValueError, match="dual-policy"):
        DecodeEngine(mono.params, cfg, None, ctx, NO_AXES,
                     EngineConfig(slots=2, cache_len=16, kv_quant="int8",
                                  speculate=2), adapter=mono)

    # a speculative round scores its drafts in one verify pass: there is
    # no per-token logit row to record
    spec = SpecSession(cfg, params, setup["policy"], ctx, kv_quant="int8")
    with pytest.raises(ValueError, match="token-at-a-time"):
        DecodeEngine(spec.params, cfg, None, ctx, NO_AXES,
                     EngineConfig(slots=2, cache_len=16, kv_quant="int8",
                                  speculate=2, record_logits=True),
                     adapter=spec)

    from repro.launch.serve import ServeConfig
    ok = ServeConfig(speculate=4, policy_path="searched.json")
    assert ok.engine_config(speculate=ok.speculate).speculate == 4
    assert ok.engine_config().speculate == 0   # reference engines never draft
    with pytest.raises(ValueError, match="--policy"):
        ServeConfig(speculate=2)
    with pytest.raises(ValueError, match="greedy"):
        ServeConfig(speculate=2, policy_path="p.json", sampling="sample")
    with pytest.raises(ValueError, match="sampling"):
        ServeConfig(sampling="nucleus")
    with pytest.raises(ValueError, match="int8"):
        ServeConfig(speculate=2, policy_path="p.json", kv="fp")
    with pytest.raises(ValueError, match="single-device"):
        ServeConfig(speculate=2, policy_path="p.json", mesh="2x4")
    with pytest.raises(ValueError, match="draft-bits"):
        ServeConfig(speculate=2, policy_path="p.json", draft_bits=1)
    with pytest.raises(ValueError, match="speculate"):
        ServeConfig(speculate=-1)


# ---------------------------------------------------------------------------
# roofline: the draft-k/verify-once round model
# ---------------------------------------------------------------------------
def test_roofline_spec_round_model(setup):
    cfg, policy, ql = setup["cfg"], setup["policy"], setup["qlayers"]
    kw = dict(cache_tokens=48, kv_bits=8.0, kv_attend="dequant",
              w_bits_total=policy.size_bytes(ql) * 8.0)
    single = roofline.decode_step_cost(cfg, 4, **kw)
    spec = roofline.decode_step_cost(cfg, 4, spec_k=4, draft_w_bits=2.0,
                                     **kw)
    # the round re-reads the tiny draft pack k times but the full target
    # pack only once; on the demo preset that beats k single steps
    assert spec["draft_hbm_bytes"] > 0 and single["draft_hbm_bytes"] == 0
    assert spec["hbm_bytes"] > single["hbm_bytes"]
    assert spec["step_s"] < 4 * single["step_s"]
    with pytest.raises(ValueError, match="spec_k"):
        roofline.decode_step_cost(cfg, 4, spec_k=-1, **kw)
    with pytest.raises(ValueError, match="sub-8-bit"):
        roofline.decode_step_cost(cfg, 4, spec_k=2, draft_w_bits=0.0, **kw)
    # a speculating engine's iteration carries more compute, so the free
    # prefill headroom per iteration cannot shrink below the single-step
    # budget on a memory-bound demo model
    chunk0 = roofline.suggest_prefill_chunk(cfg, 4, **kw)
    chunk4 = roofline.suggest_prefill_chunk(cfg, 4, spec_k=4,
                                            draft_w_bits=2.0, **kw)
    assert chunk4 >= chunk0
