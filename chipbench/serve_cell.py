"""A serving cell: the packed session behind the continuous-batching engine,
driven by closed-loop clients.

Set-up makes the weights from the seed, packs them into a
``QuantizedSession`` with the configuration's policy, builds the engine with
the cell's slots and cache, releases the float32 weights, compiles every
prompt bucket the mix can send, fills the slots and runs a few warm steps.
The window then drives ``DecodeEngine.step`` for ``--seconds``; each client
sends its next request when the last one finishes. Token times are the
client's: the time ``step`` returned with the token in hand.

After the window the engine steps on at the same load for ``STRETCH``
launches, and the harness keeps the logits each decode launch returns: the
rows the served tokens were picked from, for the check.
"""
from __future__ import annotations

import contextlib
import gc
import time

import numpy as np

import traffic

WARM_STEPS = 6
GRACE_S = 60.0
STRETCH = 12
HISTS = ("engine.decode_step_ms", "engine.prefill_ms")


class Served:
    """What the client side saw of one request."""

    __slots__ = ("prompt", "max_new", "sent", "times", "tokens", "done")

    def __init__(self, prompt, max_new, sent):
        self.prompt, self.max_new, self.sent = prompt, max_new, sent
        self.times, self.tokens, self.done = [], [], False


def buckets(mix):
    from repro.launch.scheduler import bucket_length

    lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
    out, b = [], bucket_length(lo, mix["bucket_min"])
    while True:
        out.append(min(b, mix["cache_len"]))
        if b >= hi:
            return out
        b *= 2


def p95(xs):
    return float(np.percentile(np.asarray(xs, np.float64), 95)) if xs else None


class Cell:
    def __init__(self, run):
        self.run = run
        self.mix = run.mix
        self.served = {}
        self.clients = []    # the request id each client waits on
        self.next_rid = 0
        self.it = 0
        self.recording = False
        self.decodes = []    # per decode launch in the window: the rows
        # that each live slot attended
        self.prefills = []   # per prefill in the window: prompt length
        self.logits = {}     # rid -> {served index: program's logits row}

    # -- set-up --------------------------------------------------------------
    def setup(self):
        import jax
        import jax.numpy as jnp

        from repro.core.policy import MPQPolicy
        from repro.dist.axes import NO_AXES
        from repro.launch.engine import DecodeEngine, EngineConfig
        from repro.models.quant_layers import QuantContext
        from repro.runtime.session import QuantizedSession

        run, mix, cfg = self.run, self.mix, self.run.cfg
        with run.phase("weights"):
            params = run.arch.model_params(run.seed, run.raw)
            jax.block_until_ready(params)
        with run.phase("pack"):
            qctx = QuantContext.make(cfg.bits, cfg.quant_act_signed,
                                     compute_dtype=jnp.dtype(run.compute))
            policy = MPQPolicy.from_json(run.policy_text)
            self.sess = QuantizedSession(cfg, params, policy, qctx, NO_AXES,
                                         mode="packed", kv_quant="int8")
            jax.block_until_ready(self.sess.params)
            # the float32 weights the session was packed from go before
            # the window opens
            del params
            gc.collect()
        with run.phase("engine"):
            ecfg = EngineConfig(
                slots=mix["slots"], cache_len=mix["cache_len"],
                policy="continuous", kv_quant="int8", kv_layout="ring",
                bucket_prompts=True, bucket_min=mix["bucket_min"],
                trace=False)
            self.eng = DecodeEngine(self.sess.params, cfg, None, qctx,
                                    NO_AXES, ecfg, adapter=self.sess)
        with run.phase("warm"):
            self.warm(jax, jnp)

    def warm(self, jax, jnp):
        eng = self.eng
        for b in buckets(self.mix):
            # the engine's own input op (a row of the prompt's bucket) and
            # the prefill program of every bucket the mix can send
            toks = jnp.asarray(np.zeros((b,), np.int32))[None, :]
            jax.block_until_ready(eng._prefill(eng.params, {"tokens": toks},
                                               jnp.asarray(b, jnp.int32)))
        self.stream = traffic.Stream(self.mix, self.run.seed,
                                     self.run.raw["vocab_size"])
        now = time.perf_counter()
        self.clients = [self.send(*self.stream.first(c), now)
                        for c in range(self.mix["clients"])]
        # admit every first request at once: the window opens on full slots
        chunk = eng.scheduler.prefill_chunk
        eng.scheduler.prefill_chunk = 1 << 30
        self.tick()
        eng.scheduler.prefill_chunk = chunk
        for _ in range(WARM_STEPS):
            self.tick()

    # -- the loop ------------------------------------------------------------
    def send(self, toks, max_new, now):
        from repro.launch.scheduler import Request

        rid = self.next_rid
        self.next_rid += 1
        self.served[rid] = Served(toks, max_new, now)
        self.eng.submit(Request(rid=rid, tokens=toks, max_new=max_new))
        return rid

    def tick(self, span=None):
        eng = self.eng
        span = span or (lambda name: contextlib.nullcontext())
        with span("bench.engine_step"):
            eng.step(self.it)
        self.it += 1
        now = time.perf_counter()
        with span("bench.client"):
            seen = {}
            for s in eng.slots:
                if s is not None:
                    seen[s.req.rid] = s.gen[: s.req.max_new]
            finished = list(eng.completions)
            for rid in finished:
                seen[rid] = eng.completions.pop(rid).tokens
            lens = []
            for rid, toks in seen.items():
                r = self.served[rid]
                new = toks[len(r.tokens):]
                for t in new:
                    if r.tokens:
                        # a decode token: it attended every row before it
                        lens.append(len(r.prompt) + len(r.tokens))
                    elif self.recording:
                        self.prefills.append(len(r.prompt))
                    r.tokens.append(int(t))
                    r.times.append(now)
            if self.recording and lens:
                self.decodes.append(lens)
            if finished:
                done = set(finished)
                for rid in done:
                    self.served[rid].done = True
                for c, rid in enumerate(self.clients):
                    if rid in done:
                        self.clients[c] = self.send(*self.stream.next(), now)
        return now

    def hist(self):
        out = {}
        for name in HISTS:
            h = self.eng.metrics.get(name)
            out[name] = (h.sum, h.count) if h is not None else (0.0, 0)
        return out

    def instrument(self, span):
        """Host spans around the engine's own calls, for naming idle gaps:
        admission (prefill and insert), the decode launch with its logit
        copy-back, the KV-scale drift sample, the monitor."""
        eng = self.eng

        def wrap(obj, attr, name):
            fn = getattr(obj, attr)

            def spanned(*a, **k):
                with span(name):
                    return fn(*a, **k)

            setattr(obj, attr, spanned)

        wrap(eng, "_admit", "bench.engine.admit")
        wrap(eng, "_decode_step", "bench.engine.decode")
        wrap(eng.scheduler, "admit", "bench.engine.schedule")
        wrap(eng._kv_drift, "update", "bench.engine.kv_drift")
        wrap(eng.monitor, "check", "bench.engine.monitor")

    def window(self, seconds, span=None):
        if span is not None:
            self.instrument(span)
        self.hist0 = self.hist()
        self.recording = True
        t0 = now = time.perf_counter()
        self.t0 = t0
        while now < t0 + seconds:
            now = self.tick(span)
        self.t1 = now
        self.recording = False
        self.hist1 = self.hist()

    def finish(self):
        """Where the mix reports time to first token, step on past the close
        until every request sent in the window has its first token (the
        wait counts in its time). Returns how many never got one."""
        if not self.mix.get("ttft"):
            return 0

        def waiting():
            return [r for r in self.served.values()
                    if self.t0 <= r.sent < self.t1 and not r.tokens]

        limit = time.perf_counter() + GRACE_S
        while waiting() and time.perf_counter() < limit:
            self.tick()
        return len(waiting())

    def stretch(self, steps=STRETCH):
        """Step on past the close at the window's load, keeping the logits
        of every live slot that each decode launch returns."""
        eng = self.eng
        launch = eng._decode
        got = []

        def keep(params, toks, pos, state):
            logits, state = launch(params, toks, pos, state)
            live = [(i, s.req.rid, len(s.gen)) for i, s in
                    enumerate(eng.slots) if s is not None and not s.done]
            got.append((live, logits))
            return logits, state

        eng._decode = keep
        try:
            for _ in range(steps):
                self.tick()
        finally:
            eng._decode = launch
        for live, logits in got:
            host = np.asarray(logits, np.float32)
            for i, rid, j in live:
                self.logits.setdefault(rid, {})[j] = host[i]

    # -- end-to-end readings ---------------------------------------------------
    def readings(self):
        t0, t1 = self.t0, self.t1
        toks, gaps, ttft, sent = 0, [], [], 0
        for r in self.served.values():
            ts = r.times
            toks += sum(1 for t in ts if t0 < t <= t1)
            gaps += [b - a for a, b in zip(ts, ts[1:]) if a >= t0 and b <= t1]
            if t0 <= r.sent < t1:
                sent += 1
                if ts:
                    ttft.append(ts[0] - r.sent)
        e2e = {"tokens_per_s": toks / (t1 - t0)}
        if gaps:
            e2e["itl_p95_ms"] = p95(gaps) * 1e3
        if ttft:
            e2e["ttft_p95_ms"] = p95(ttft) * 1e3
        print(f"window {t1 - t0:.3f} s: {toks} tokens, {len(gaps)} gaps, "
              f"{sent} requests sent, {len(ttft)} first tokens, "
              f"{len(self.decodes)} decode launches, {len(self.prefills)} "
              f"prefills", flush=True)
        return {"e2e": e2e, "attempted": sent + self.open_at_start()}

    def open_at_start(self):
        return sum(1 for r in self.served.values()
                   if r.sent < self.t0 and (not r.times or r.times[-1] > self.t0))

    def check_sample(self):
        """Requests for the reference, each as (prompt, served tokens,
        program's logits rows by served index): finished ones, the one with
        the most served tokens and others drawn from the seed; then ones
        the stretch after the window served, drawn from the seed, with the
        rows it kept."""
        rng = np.random.default_rng([self.run.seed & (2**64 - 1), 4])
        done = sorted((rid for rid, r in self.served.items() if r.done),
                      key=lambda rid: (-len(self.served[rid].tokens), rid))
        pick = []
        if done:
            rest = [done[i + 1] for i in rng.permutation(len(done) - 1)]
            pick = [done[0]] + rest[: self.mix["check_requests"] - 1]
        kept = sorted(rid for rid in self.logits if rid not in pick)
        pick += [kept[i] for i in rng.permutation(len(kept))[
            : self.mix["check_rows_requests"]]]
        out = []
        for rid in pick:
            r = self.served[rid]
            rows = {j: v for j, v in self.logits.get(rid, {}).items()
                    if j < len(r.tokens)}
            out.append((r.prompt, r.tokens, rows))
        return out

    def release(self):
        del self.eng, self.sess
        gc.collect()

    def decode_cost(self):
        """Bytes accessed per launch of the compiled decode program, by
        XLA's cost analysis."""
        import jax.numpy as jnp

        if not self.run.args.trace:
            return None
        eng, n = self.eng, self.mix["slots"]
        c = eng._decode.lower(eng.params, jnp.zeros((n, 1), jnp.int32),
                              jnp.zeros((n,), jnp.int32),
                              eng.state).compile().cost_analysis()
        c = c[0] if isinstance(c, (list, tuple)) else c
        return float(c.get("bytes accessed", 0.0))


class Reading:
    """What a per-layer reader may read (``chipbench/metrics/*.py``)."""

    def __init__(self, run, cell, red, peaks, e2e):
        self.raw, self.policy, self.mix = run.raw, run.policy, run.mix
        self.arch = run.arch
        self.client = e2e  # the client's own readings of the window
        self.trace, self.peaks = red, peaks
        self.decodes, self.prefills = cell.decodes, cell.prefills
        self.window_s = red.window_s
        self.hist = {k: (cell.hist1[k][0] - cell.hist0[k][0],
                         cell.hist1[k][1] - cell.hist0[k][1])
                     for k in HISTS}
        self.cell = cell

    def mean_ms(self, name):
        s, n = self.hist[name]
        return s / n if n else None

    @property
    def decode_bytes(self):
        return self.cell.decode_cost()


def stats(gaps, rows, refs):
    """The numbers compared. ``gap_max``: the widest gap by which a served
    token's logit lies below the reference's best. ``dev_ms``: of each kept
    row of logits against the reference's row at the same position, the
    difference less its mean over the vocabulary (a shift common to every
    logit picks the same token), squared and averaged over every entry of
    every row; ``dev_rms`` is its square root. ``off_top``: the share of
    served tokens that are not the reference's first."""
    g = np.asarray(gaps, np.float64)
    out = {"gap_max": float(g.max()) if g.size else None,
           "off_top": float((g > 0).mean()) if g.size else None,
           "dev_ms": None, "dev_rms": None}
    if rows:
        d = np.stack(rows).astype(np.float64) - np.stack(refs)
        d -= d.mean(-1, keepdims=True)
        ms = float((d * d).mean())
        out.update(dev_ms=ms, dev_rms=ms ** 0.5)
    return out


def held(checks) -> bool:
    """Every number compared is there and within its limit."""
    return bool(checks) and all(
        None not in (c["value"], c["limit"]) and c["value"] <= c["limit"]
        for c in checks.values())


def check(run, sample, lim, controls):
    """Hold what ``sample`` served to the plain reference. Returns the
    numbers compared, each with its limit, and the controls' readings."""
    res = {"program": [], "rows": []}
    res.update({c: {"gaps": [], "rows": []} for c in controls})
    if sample:
        res = run.arch.score(
            run.seed, run.raw, run.policy,
            [(p, t, sorted(rows)) for p, t, rows in sample],
            run.mix["cache_len"], run.mix["output"]["max"], controls)
    prog_rows = [rows[j] for _, _, rows in sample for j in sorted(rows)]
    prog = stats(res["program"], prog_rows, res["rows"])
    print(f"reference: {len(sample)} requests, {len(res['program'])} served "
          f"tokens, {len(prog_rows)} rows of logits; program {prog}",
          flush=True)
    names = list(lim) or list(prog)
    checks = {k: {"value": prog.get(k), "limit": lim.get(k, {}).get("limit")}
              for k in names}
    ctl = {}
    for c in controls:
        # the reference in a lower precision, put in the program's place
        # and held to the same limits
        got = stats(res[c]["gaps"], res[c]["rows"], res["rows"])
        got["correct"] = held({k: {"value": got.get(k), "limit": v["limit"]}
                               for k, v in checks.items()})
        ctl[c] = got
        print(f"control {c}: {got}", flush=True)
    return checks, ctl
