"""Observability subsystem: metrics-registry math, trace schema
round-trips, stats/trace reconciliation on a real engine run, and the
roofline calibration loop."""
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.dist import roofline
from repro.dist.axes import NO_AXES
from repro.launch.engine import DecodeEngine, EngineConfig, EngineStats
from repro.launch.scheduler import Request
from repro.models import lm
from repro.models.quant_layers import QuantContext
from repro.obs import calibrate, metrics, trace


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------
def test_counter_monotonic():
    c = metrics.Counter("c")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    assert c.value == 3.5


def test_gauge_moves_both_ways():
    g = metrics.Gauge("g")
    g.set(5)
    g.set(2)
    assert g.value == 2.0


def test_histogram_bucket_assignment():
    h = metrics.Histogram("h", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.0, 1.5, 3.0, 100.0):
        h.observe(v)
    # upper-bound-inclusive buckets plus the implicit overflow
    assert h.counts == [2, 1, 1, 1]
    assert h.count == 5
    assert h.sum == pytest.approx(106.0)
    d = h.as_dict()
    assert d["min"] == 0.5 and d["max"] == 100.0
    assert d["buckets"]["+inf"] == 1


def test_histogram_rejects_bad_buckets():
    with pytest.raises(ValueError):
        metrics.Histogram("h", buckets=(2.0, 1.0))
    with pytest.raises(ValueError):
        metrics.Histogram("h", buckets=())
    with pytest.raises(ValueError):
        metrics.Histogram("h", buckets=(1.0, math.inf))


def test_histogram_percentiles():
    h = metrics.Histogram("h", buckets=(10.0, 20.0, 30.0, 40.0))
    assert h.percentile(0.5) == 0.0          # empty
    h.observe(25.0)
    # a single sample reports itself: edges clamp to observed min/max
    assert h.percentile(0.0) == pytest.approx(25.0)
    assert h.percentile(0.5) == pytest.approx(25.0)
    assert h.percentile(1.0) == pytest.approx(25.0)
    h2 = metrics.Histogram("h2", buckets=(10.0, 20.0, 30.0, 40.0))
    for v in range(1, 101):                  # uniform over (0, 100]
        h2.observe(float(v))
    # interpolated percentiles track the uniform distribution to within
    # a bucket width; p100 is exactly the observed max
    assert h2.percentile(0.50) == pytest.approx(50.0, abs=10.0)
    assert h2.percentile(0.95) == pytest.approx(95.0, abs=10.0)
    assert h2.percentile(1.0) == pytest.approx(100.0)
    with pytest.raises(ValueError):
        h2.percentile(1.5)


def test_registry_get_or_create_and_typing():
    reg = metrics.MetricsRegistry()
    assert reg.counter("a") is reg.counter("a")
    reg.counter("a").inc(3)
    assert reg.value("a") == 3.0
    assert reg.value("missing") == 0.0
    with pytest.raises(TypeError):
        reg.gauge("a")
    with pytest.raises(TypeError):
        reg.histogram("a")
    reg.gauge("g").set(7)
    reg.histogram("h").observe(1.0)
    snap = reg.snapshot()
    assert snap["a"] == 3.0 and snap["g"] == 7.0
    assert snap["h"]["count"] == 1
    json.dumps(snap)  # JSON-able end to end
    assert "a" in reg and len(reg) == 3


# ---------------------------------------------------------------------------
# trace schema round-trips
# ---------------------------------------------------------------------------
def _demo_recorder():
    rec = trace.TraceRecorder()
    rec.instant("admit", track=trace.req_track(0), ts=0.0, rid=0,
                prompt_len=4)
    rec.span("prefill", 0.0, 0.5, track=trace.req_track(0), rid=0)
    rec.instant("first_token", track=trace.req_track(0), ts=0.5, rid=0,
                token=7)
    rec.span("decode_step", 0.5, 0.75, slots=1)
    rec.instant("token", track=trace.req_track(0), ts=0.75, rid=0, token=3)
    rec.instant("complete", track=trace.req_track(0), ts=0.75, rid=0)
    return rec


def test_span_rejects_negative_duration():
    rec = trace.TraceRecorder()
    with pytest.raises(ValueError):
        rec.span("x", 1.0, 0.5)


def test_jsonl_round_trip(tmp_path):
    rec = _demo_recorder()
    path = str(tmp_path / "t.jsonl")
    rec.to_jsonl(path)
    back = trace.TraceRecorder.from_jsonl(path)
    assert back.events == rec.events


def test_jsonl_rejects_unknown_schema(tmp_path):
    path = str(tmp_path / "t.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"schema": 999}) + "\n")
    with pytest.raises(ValueError):
        trace.TraceRecorder.from_jsonl(path)


def test_chrome_round_trip(tmp_path):
    rec = _demo_recorder()
    obj = rec.chrome()
    assert trace.validate_chrome(obj) == []
    # thread-name metadata labels every track
    names = {m["args"]["name"] for m in obj["traceEvents"]
             if m.get("ph") == "M"}
    assert trace.ENGINE_TRACK in names and "req:0" in names
    back = trace.TraceRecorder.from_chrome(obj)
    assert [(e.name, e.track) for e in back.events] == \
        [(e.name, e.track) for e in rec.events]
    for a, b in zip(back.events, rec.events):
        assert a.ts == pytest.approx(b.ts)
        assert a.dur == pytest.approx(b.dur)
        assert a.args == b.args
    # extension-based writer: .jsonl vs chrome json
    p1, p2 = str(tmp_path / "a.jsonl"), str(tmp_path / "a.json")
    rec.write(p1)
    rec.write(p2)
    assert trace.TraceRecorder.from_jsonl(p1).events == rec.events
    assert trace.validate_chrome(json.load(open(p2))) == []


def test_request_summaries():
    rec = _demo_recorder()
    reqs = trace.request_summaries(rec.events)
    assert set(reqs) == {0}
    r = reqs[0]
    assert r["tokens"] == 2
    assert r["ttft_ms"] == pytest.approx(500.0)
    assert r["itl_ms"] == [pytest.approx(250.0)]


def test_reconcile_flags_mismatches():
    rec = _demo_recorder()
    good = {"t_decode_s": 0.25, "t_prefill_s": 0.5, "decode_steps": 1,
            "tokens_generated": 2, "admitted": 1, "completed": 1}
    assert trace.reconcile(rec, good) == []
    bad = dict(good, t_decode_s=1.0, tokens_generated=5)
    problems = trace.reconcile(rec, bad)
    assert any("t_decode_s" in p for p in problems)
    assert any("tokens_generated" in p for p in problems)


# ---------------------------------------------------------------------------
# engine integration: lifecycle spans + counters on a real run
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def served():
    cfg = smoke_config("limpq-demo")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    ctx = QuantContext.make(cfg.bits, cfg.quant_act_signed,
                            compute_dtype=jnp.float32)
    bits = lm.bits_uniform(cfg, 4)
    eng = DecodeEngine(params, cfg, bits, ctx, NO_AXES,
                       EngineConfig(slots=2, cache_len=24))
    data_rng = np.random.default_rng(0)
    reqs = [Request(rid=i, tokens=data_rng.integers(
                0, cfg.vocab, size=8 - i).astype(np.int32), max_new=3 + i)
            for i in range(3)]
    eng.submit_all(reqs)
    completions = eng.run()
    return dict(cfg=cfg, eng=eng, reqs=reqs, completions=completions)


def test_engine_trace_complete_lifecycles(served):
    eng = served["eng"]
    stats = eng.stats
    problems = trace.reconcile(eng.trace, stats.as_dict())
    assert problems == [], problems
    reqs = trace.request_summaries(eng.trace.events)
    assert set(reqs) == {r.rid for r in served["reqs"]}
    for rid, r in reqs.items():
        # full admit -> first_token -> tokens -> complete -> evict chain,
        # timestamps non-decreasing
        for stage in ("admit", "first_token", "complete", "evict"):
            assert stage in r, (rid, stage)
        chain = [r["admit"], r["first_token"]] + sorted(r["token_ts"]) + \
            [r["complete"], r["evict"]]
        assert all(b >= a for a, b in zip(chain, chain[1:])), (rid, chain)
        assert r["tokens"] == len(served["completions"][rid].tokens)
    # decode spans carry the fenced step timings exactly
    decode_durs = [e.dur for e in eng.trace.events
                   if e.name == "decode_step"]
    assert len(decode_durs) == stats.decode_steps
    assert sum(decode_durs) == pytest.approx(stats.t_decode_s, rel=1e-6)


def test_engine_stats_snapshot_and_latency(served):
    eng = served["eng"]
    s = eng.stats
    assert isinstance(s, EngineStats)
    assert s.tokens_generated == sum(
        len(c.tokens) for c in served["completions"].values())
    d = s.as_dict()
    for key in ("ttft_p50_ms", "ttft_p95_ms", "itl_p50_ms", "itl_p95_ms",
                "decode_step_p50_ms", "prefill_p50_ms"):
        assert key in d and d[key] > 0.0, key
    assert d["ttft_p50_ms"] <= d["ttft_p95_ms"]
    # timers are perf_counter based and cover the histograms' mass
    assert s.t_decode_s > 0.0 and s.t_prefill_s > 0.0
    # scheduler + dispatch instrumented through the same registry
    assert eng.metrics.value("scheduler.admitted") == s.admitted
    assert "scheduler.queue_depth" in eng.metrics
    assert eng.metrics.value(
        f"engine.decode_attn_route.{eng.decode_attn_route}") == 1.0


def test_engine_ttft_counts_the_queue_wait(served):
    """TTFT runs from ``submit`` to the first token: with one slot, the
    second request waits out the first's whole service, which the fenced
    prefill alone (``engine.prefill_ms``) does not see."""
    cfg = served["cfg"]
    ctx = QuantContext.make(cfg.bits, cfg.quant_act_signed,
                            compute_dtype=jnp.float32)
    eng = DecodeEngine(served["eng"].params, cfg, lm.bits_uniform(cfg, 4),
                       ctx, NO_AXES, EngineConfig(slots=1, cache_len=24))
    reqs = [Request(rid=i, tokens=np.arange(1, 7, dtype=np.int32) + i,
                    max_new=4) for i in range(2)]
    eng.submit_all(reqs)
    eng.run()
    m = eng.metrics
    ttft = m.get("engine.ttft_ms").as_dict()
    prefill = m.get("engine.prefill_ms").as_dict()
    decode = m.get("engine.decode_step_ms").as_dict()
    assert m.get("engine.ttft_ms").count == 2
    # the first request's prefill and three decode launches, then the
    # second's own prefill
    assert ttft["max"] >= 2 * prefill["min"] + 3 * decode["min"]
    assert m.get("engine.ttft_ms").sum > m.get("engine.prefill_ms").sum


def test_engine_reset_starts_fresh_epoch(served):
    eng = served["eng"]
    old_stats = eng.stats
    old_registry = eng.metrics
    old_trace = eng.trace
    assert old_stats.completed > 0
    eng.reset()
    # new epoch: counters restart from zero, the old snapshot (and the old
    # registry/trace objects) stay frozen rather than being rewound
    assert eng.metrics is not old_registry
    assert eng.trace is not old_trace
    assert eng.stats.completed == 0
    assert eng.stats.iterations == 0
    assert old_stats.completed > 0
    assert old_registry.value("engine.completed") == old_stats.completed
    # re-serve after reset to leave the fixture engine usable
    eng.submit_all(served["reqs"])
    eng.run()
    assert eng.stats.completed == len(served["reqs"])


# ---------------------------------------------------------------------------
# roofline calibration
# ---------------------------------------------------------------------------
def test_calibrate_finite_rows_and_device_table(served):
    eng, cfg = served["eng"], served["cfg"]
    report = calibrate.calibrate(
        cfg, eng.stats.as_dict(), slots=eng.ecfg.slots,
        cache_tokens=eng.ecfg.cache_len, kv_bits=eng.kv_bits,
        kv_attend=eng.kv_attend, chip=eng.ecfg.chip)
    assert report["finite"]
    assert {r["phase"] for r in report["rows"]} == \
        {"decode_step", "prefill_token", "ttft"}
    for r in report["rows"]:
        assert math.isfinite(r["ratio"]) and r["ratio"] > 0, r
    t = report["device_table"]
    assert t["hbm_bytes_s"] > 0 and t["peak_flops"] > 0
    chip = roofline.chip_from_table(t)
    assert chip.hbm_bytes_s == pytest.approx(t["hbm_bytes_s"])
    assert chip.peak_flops == pytest.approx(t["peak_flops"])
    assert chip.ici_bytes_s == roofline.DEFAULT_CHIP.ici_bytes_s
    table = calibrate.render_table(report["rows"])
    assert "decode_step" in table and "ratio" in table


def test_chip_from_table_rejects_nonpositive():
    with pytest.raises(ValueError):
        roofline.chip_from_table({"hbm_bytes_s": 0.0})
    with pytest.raises(ValueError):
        roofline.chip_from_table({"peak_flops": -1.0})
    # bookkeeping keys ignored, name passthrough allowed
    chip = roofline.chip_from_table(
        {"name": "x-measured", "source": "unit-test"})
    assert chip.name == "x-measured"
