"""The engine's host spans, read back from a ``jax.profiler`` trace on the
CPU: one ``engine.step`` per iteration, each phase nested where it belongs
(schedule, admit, decode, KV drift, bookkeeping, monitor under the step;
the jitted launch and the token copy-back under an admission or a decode),
in the plain, paged and speculative paths, and none per slot. The trace
recorder's spans sit on the profiler's clock through its wall-clock
anchor."""
import glob
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core.policy import MPQPolicy
from repro.dist.axes import NO_AXES
from repro.launch.engine import DecodeEngine, EngineConfig
from repro.launch.scheduler import Request
from repro.models import lm
from repro.models.quant_layers import QuantContext
from repro.obs import trace as obs_trace
from repro.runtime.session import SpecSession

CHILDREN = {"engine.schedule", "engine.admit", "engine.decode",
            "engine.kv_drift", "engine.bookkeeping", "engine.monitor"}
INNER = {"engine.launch", "engine.sample"}


def _host_events(trace_dir, prefix):
    """(start unix ns, end unix ns, name) of the host events whose name
    starts with ``prefix``, on the profiler's wall clock."""
    from jax.profiler import ProfileData

    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    pd = ProfileData.from_file(path)
    base = None
    out = []
    for plane in pd.planes:
        if plane.name == "Task Environment":
            base = dict(plane.stats)["profile_start_time"]
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                        for ev in line.events if ev.name.startswith(prefix)]
    return sorted((base + a, base + b, n) for a, b, n in out)


def _tree(spans):
    """Each span's parent (the innermost span holding it), by index."""
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i][0], -spans[i][1]))
    parent, stack = {}, []
    for i in order:
        while stack and spans[stack[-1]][1] < spans[i][1]:
            stack.pop()
        parent[i] = stack[-1] if stack else None
        stack.append(i)
    return parent


@pytest.fixture(scope="module")
def spec():
    cfg = smoke_config("limpq-demo")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    ctx = QuantContext.make(cfg.bits, cfg.quant_act_signed,
                            compute_dtype=jnp.float32)
    ql = lm.enumerate_qlayers(cfg)
    policy = MPQPolicy({q.name: (4 if i % 2 else 6) for i, q in enumerate(ql)},
                       {q.name: 4 for q in ql})
    sess = SpecSession(cfg, params, policy, ctx, draft_w_bits=2,
                       kv_quant="int8")
    return cfg, ctx, sess


@pytest.mark.parametrize("layout, k, recorded", [
    ("ring", 0, False), ("paged", 0, True),
    ("ring", 2, False), ("paged", 2, True),
])
def test_engine_spans_nest_once_per_iteration(spec, tmp_path, layout, k,
                                              recorded):
    cfg, ctx, sess = spec
    eng = DecodeEngine(sess.params, cfg, None, ctx, NO_AXES,
                       EngineConfig(slots=2, cache_len=32, kv_quant="int8",
                                    kv_layout=layout, page_size=8,
                                    speculate=k, trace=recorded,
                                    health_every=2),
                       adapter=sess)
    rng = np.random.default_rng(3)
    reqs = [Request(rid=i, tokens=rng.integers(0, cfg.vocab, size=6 + i)
                    .astype(np.int32), max_new=5 + i) for i in range(3)]
    # compile outside the trace
    eng.submit_all(reqs)
    eng.run()
    eng.reset()
    eng.submit_all(reqs)
    iters = 0
    with jax.profiler.trace(str(tmp_path)):
        while eng.step(iters):
            iters += 1
    iters += 1   # the last call found nothing to do
    spans = _host_events(str(tmp_path), "engine.")
    names = [n for _, _, n in spans]
    stats = eng.stats
    assert names.count("engine.step") == iters
    assert names.count("engine.admit") == stats.admitted == len(reqs)
    assert names.count("engine.decode") == stats.decode_steps
    assert names.count("engine.kv_drift") == stats.decode_steps // 2
    # one launch and one copy-back per admission and per decode: no span
    # per slot or per token
    per = stats.admitted + stats.decode_steps
    assert names.count("engine.launch") == names.count("engine.sample") == per
    assert names.count("engine.bookkeeping") <= 2 * iters
    parent = _tree(spans)
    for i, (_, _, n) in enumerate(spans):
        up = parent[i]
        if n == "engine.step":
            assert up is None
        elif n in CHILDREN:
            assert spans[up][2] == "engine.step", n
        else:
            assert n in INNER, n
            assert spans[up][2] in ("engine.admit", "engine.decode"), n
    if recorded:
        # the recorder keeps the same spans, on the same wall clock
        rec = [(eng.trace.unix_ns(e.ts), e.name) for e in eng.trace.events
               if e.name.startswith("engine.")]
        assert sorted(n for _, n in rec) == sorted(names)
        first = min(rec)
        assert abs(first[0] - spans[0][0]) < 1e6


def test_recorder_span_lands_on_the_profiler_clock(tmp_path):
    rec = obs_trace.TraceRecorder()
    time.sleep(0.01)
    with jax.profiler.trace(str(tmp_path)):
        with obs_trace.span("engine.anchor", rec):
            time.sleep(0.02)
    (a, b, _), = _host_events(str(tmp_path), "engine.anchor")
    ev, = [e for e in rec.events if e.name == "engine.anchor"]
    assert abs(rec.unix_ns(ev.ts) - a) < 1e6
    assert abs(rec.unix_ns(ev.end()) - b) < 1e6
    # the anchor rides in both exports
    back = obs_trace.TraceRecorder.from_chrome(rec.chrome())
    assert back.epoch_unix_ns == rec.epoch_unix_ns
    assert rec.chrome()["metadata"]["epoch_unix_ns"] == rec.epoch_unix_ns
    path = str(tmp_path / "t.jsonl")
    rec.to_jsonl(path)
    assert obs_trace.TraceRecorder.from_jsonl(path).epoch_unix_ns == \
        rec.epoch_unix_ns


def test_span_without_profiler_or_recorder_is_transparent():
    with pytest.raises(KeyError):
        with obs_trace.span("engine.step"):
            raise KeyError("passes through")
    rec = obs_trace.TraceRecorder()
    with pytest.raises(KeyError):
        with obs_trace.span("engine.step", rec):
            raise KeyError("recorded, then passes through")
    assert [e.name for e in rec.events] == ["engine.step"]


def test_fused_spec_round_scopes_its_halves(spec):
    cfg, ctx, sess = spec
    eng = DecodeEngine(sess.params, cfg, None, ctx, NO_AXES,
                       EngineConfig(slots=2, cache_len=32, kv_quant="int8",
                                    speculate=2, trace=False),
                       adapter=sess)
    z = jnp.zeros((2,), jnp.int32)
    text = eng._spec_fused(2).lower(
        eng.params, eng.draft_params, z[:, None], z, z, eng.state
    ).as_text(debug_info=True)
    for half in ("spec_draft", "spec_verify"):
        assert f'/{half}/' in text, half
