"""Serving driver: request-queue front-end over the continuous-batching
decode engine (``repro.launch.engine``), with a mixed-precision policy
active (CPU-runnable demo of the deployment path).

The legacy fixed-batch loop is now one scheduling policy among several
(``--schedule fixed``); the default is continuous batching with
roofline-driven prefill/decode interleave. ``--compare`` (implied by
``--smoke``) runs the same request set under both schedules, checks the
generated tokens are identical, and reports the decode steps saved.

``--policy <searched.json>`` switches to the quantized serving runtime:
the policy compiles into a ``repro.runtime.session.QuantizedSession``
(weights quantized onto the searched per-layer grids, sub-8-bit codes
bit-packed, int8 KV-cache slots, prompt-length bucketing) and serves
through the same engine. With ``--check`` (implied by ``--smoke``, which
also shrinks the model) that path is gated hard: greedy tokens must be
identical to a reference engine running the fake-quant training graph, and
measured packed HBM bytes must land within 5% of ``MPQPolicy.size_bytes``.
Where the Pallas routes ran (a TPU), each kernel is first held to its
``dequant-fp`` route op by op (``runtime.parity``), the token gate serves
the session again with every route forced to ``dequant-fp``, and the
logits the measured engine recorded are held within ``DRIFT_BOUND`` of an
f32 reference traced at matmul precision ``highest``.

``--mesh <name>`` serves under a real device mesh (``host`` = trivial
(1,); ``host8`` = 2-way data x 4-way tensor parallel over 8 forced host
devices): packed codes/scales shard per-tensor-parallel-shard, the int8
KV slot axis shards over data, and the engine jits with explicit
in/out_shardings. The smoke then adds a per-chip gate: per-shard packed
bytes must not exceed ``policy.size_bytes / tp`` beyond padding, while
greedy tokens stay identical to the single-device reference.

``--decode-attn`` pins how the int8 KV cache is attended
(``runtime.dispatch.resolve_decode_attn``): ``fused`` is the Pallas
kernel reading codes directly (TPU), ``fused-interpret`` runs the same
kernel program through the interpreter (the CI proof that the fused route
stays greedy-token-identical to the reference), ``dequant-fp`` is the
exact fallback, ``auto`` (default) resolves by backend.

``--speculate k`` turns on self-speculative decoding over the ``--policy``
runtime: the session packs a second, uniform low-bit policy
(``--draft-bits``, default int2) over the SAME weights and indicator-bank
scales, the draft proposes k tokens autoregressively, and the searched
target policy verifies all k in one batched multi-token step sharing the
int8 KV cache (draft-written rows past the first rejection are rolled
back). Greedy acceptance keeps the output token-identical to
non-speculative decode; with ``--smoke`` that identity is gated hard.

Examples:
  python -m repro.launch.serve --smoke
  python -m repro.launch.serve --write-demo-policy searched.json
  python -m repro.launch.serve --smoke --policy searched.json
  python -m repro.launch.serve --smoke --policy searched.json \
      --decode-attn fused-interpret
  python -m repro.launch.serve --smoke --policy searched.json \
      --speculate 4 --kv-layout paged --decode-attn fused-interpret
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python -m repro.launch.serve --smoke --policy searched.json \
      --mesh host8
  python -m repro.launch.serve --arch limpq-demo --requests 8 --slots 4 \
      --prompt-len 32 --gen 16 --stagger --compare
"""
from __future__ import annotations

import argparse
import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, smoke_config
from repro.core.policy import MPQPolicy
from repro.data import SyntheticLM
from repro.dist.axes import NO_AXES
from repro.launch import compile_cache
from repro.launch.engine import DecodeEngine, EngineConfig
from repro.launch.scheduler import POLICIES, Request
from repro.models import lm
from repro.models.quant_layers import QuantContext
from repro.runtime import dispatch


@dataclasses.dataclass
class ServeConfig:
    """The serving flag pile as one typed, validated object.

    ``main()`` builds it from argparse (``from_args``); tests, benchmarks
    and ``runtime.sharded_smoke`` build it directly — either way, engine
    construction consumes ``engine_config()`` instead of re-plumbing loose
    knobs, so a new serving option lands in every harness at once.
    Route-shaped fields (``kv_layout``, ``decode_attn``) validate against
    ``runtime.dispatch.ROUTES`` at construction, not deep in the engine.
    """

    arch: str = "limpq-demo"
    requests: int = 8
    slots: int = 4
    prompt_len: int = 32
    gen: int = 16
    cache_len: int = 0          # 0 = prompt + gen
    schedule: str = "continuous"
    stagger: bool = False
    arrive_every: int = 0
    policy_path: Optional[str] = None
    kv: str = "int8"            # int8 | fp: --policy runtime KV storage
    kv_layout: str = "ring"     # ring | paged (dispatch.ROUTES registry)
    page_size: int = 8          # tokens per KV page (paged only)
    decode_attn: str = "auto"   # auto | a dispatch decode_attn route
    mesh: Optional[str] = None
    bucket: bool = True         # prompt-length bucketing (ring only)
    chip_table: Optional[str] = None  # measured device table json (roofline)
    speculate: int = 0          # self-speculative draft length k (0 = off)
    draft_bits: int = 2         # draft policy weight bits (--speculate)
    elastic: bool = False       # admission-time ILP re-solve + hot-swap
    policy_variants: str = "3,4,6"  # avg weight-bit budgets of the bank
    sampling: str = "greedy"    # token selection; only greedy exists today
    seed: int = 0

    def __post_init__(self):
        if self.schedule not in POLICIES:
            raise ValueError(
                f"unknown schedule {self.schedule!r}; known: {POLICIES}")
        if self.kv not in ("int8", "fp"):
            raise ValueError(f"kv must be 'int8' or 'fp', got {self.kv!r}")
        dispatch.ROUTES.validate("kv_layout", self.kv_layout)
        if self.decode_attn != "auto":
            dispatch.ROUTES.validate("decode_attn", self.decode_attn)
        if self.kv_layout == "paged":
            if self.kv == "fp":
                raise ValueError(
                    "--kv-layout paged requires --kv int8: pages hold "
                    "int8 codes + scales")
            if self.mesh:
                raise ValueError(
                    "--kv-layout paged is single-device for now: the page "
                    "pool id space is not mesh-sharded")
        if self.speculate < 0:
            raise ValueError(f"--speculate must be >= 0, got {self.speculate}")
        dispatch.ROUTES.validate("spec", "self" if self.speculate else "off")
        if self.speculate:
            # every incompatibility is rejected HERE, with the reason, not
            # deep in the engine as a shape error three jits later
            if self.sampling != "greedy":
                raise ValueError(
                    "--speculate requires greedy sampling: acceptance "
                    "compares the draft token against the target argmax, "
                    "which is only token-identity-preserving when the "
                    "non-speculative path is also argmax")
            if not self.policy_path:
                raise ValueError(
                    "--speculate needs --policy <searched.json>: the draft "
                    "is a low-bit repack of the SAME packed weights "
                    "(runtime.session.SpecSession), so there must be a "
                    "packed target policy to draft for")
            if self.kv == "fp":
                raise ValueError(
                    "--speculate requires --kv int8: draft and verify share "
                    "one int8 KV cache (draft rows are overwritten by the "
                    "verify pass, rolled back past the first rejection)")
            if self.mesh:
                raise ValueError(
                    "--speculate is single-device for now: the fused "
                    "draft-verify round does not shard")
            if not (2 <= self.draft_bits <= 8):
                raise ValueError(
                    f"--draft-bits must be in [2, 8], got {self.draft_bits}; "
                    "it must also be one of the arch's searched bit-widths "
                    "so the draft grid shares the indicator-bank scales "
                    "(checked against the config at session build)")
            if self.kv_layout == "paged":
                # rollback support is a cache-protocol capability, not a
                # given: a paged pool without COW tail truncation would
                # corrupt shared-prefix pages on rejection
                from repro.runtime.kv_cache import PagedKVCache
                if not callable(getattr(PagedKVCache, "rollback", None)):
                    raise ValueError(
                        "--speculate with --kv-layout paged needs "
                        "PagedKVCache.rollback (drop/COW-truncate the tail "
                        "pages past the first rejection); this build's "
                        "paged cache does not support it")
        elif self.sampling != "greedy":
            raise ValueError(
                f"unknown sampling mode {self.sampling!r}; the engine "
                "decodes greedily (argmax)")
        dispatch.ROUTES.validate("elastic", "bank" if self.elastic else "off")
        if self.elastic:
            if not self.policy_path:
                raise ValueError(
                    "--elastic needs --policy <searched.json>: the variant "
                    "bank searches its budgets over the SAME indicator "
                    "banks the base policy was searched from, and the base "
                    "policy anchors that family")
            if self.speculate:
                raise ValueError(
                    "--elastic is incompatible with --speculate: the draft "
                    "pack pairs with ONE target policy and would go stale "
                    "at the first hot-swap")
            if self.mesh:
                raise ValueError(
                    "--elastic is single-device for now: a hot-swap would "
                    "have to re-place every packed shard on the mesh")
            if self.schedule == "fixed":
                raise ValueError(
                    "--elastic needs a continuous schedule: the controller "
                    "re-solves against the live admission stream, which "
                    "the fixed policy drains in whole rounds")
            if self.kv == "fp":
                raise ValueError(
                    "--elastic requires --kv int8: the variant bank is a "
                    "packed-session feature (pre-packed trees to swap)")
            self.variant_budgets  # malformed --policy-variants fails HERE

    @property
    def variant_budgets(self) -> Tuple[float, ...]:
        """``--policy-variants`` parsed to sorted avg weight-bit budgets."""
        try:
            vals = tuple(float(x) for x in self.policy_variants.split(","))
        except ValueError:
            raise ValueError(
                "--policy-variants must be comma-separated average "
                f"weight-bit budgets, got {self.policy_variants!r}")
        if len(vals) < 2 or len(set(vals)) != len(vals):
            raise ValueError(
                "--policy-variants needs >= 2 distinct budgets "
                f"(a one-variant bank cannot degrade), got "
                f"{self.policy_variants!r}")
        return tuple(sorted(vals))

    @property
    def resolved_cache_len(self) -> int:
        return self.cache_len or (self.prompt_len + self.gen)

    @property
    def session_kv(self) -> str:
        """KV storage mode for the packed session (``--kv`` normalized)."""
        return "none" if self.kv == "fp" else "int8"

    @classmethod
    def from_args(cls, args) -> "ServeConfig":
        return cls(
            arch=args.arch, requests=args.requests, slots=args.slots,
            prompt_len=args.prompt_len, gen=args.gen,
            cache_len=args.cache_len, schedule=args.schedule,
            stagger=args.stagger, arrive_every=args.arrive_every,
            policy_path=args.policy, kv=args.kv, kv_layout=args.kv_layout,
            page_size=args.page_size, decode_attn=args.decode_attn,
            mesh=args.mesh, bucket=not args.no_bucket,
            chip_table=args.chip_table, speculate=args.speculate,
            draft_bits=args.draft_bits, elastic=args.elastic,
            policy_variants=args.policy_variants, seed=args.seed)

    @property
    def chip(self):
        """``--chip-table`` resolved to a calibrated ``ChipSpec`` (cached);
        None without a table. Accepts either a bare device-table stanza or
        a whole ``benchmarks/roofline_calibration.py`` bench JSON (the
        ``device_table`` key)."""
        if self.chip_table is None:
            return None
        if not hasattr(self, "_chip"):
            self._chip = load_chip_table(self.chip_table)
        return self._chip

    def engine_config(self, *, kv_quant: Optional[str] = None,
                      schedule: Optional[str] = None,
                      layout: Optional[str] = None,
                      calibrated: bool = True,
                      speculate: int = 0,
                      record_logits: bool = False) -> EngineConfig:
        """An ``EngineConfig`` for one engine of this serving run.

        ``kv_quant`` defaults to the packed session's storage mode; a
        non-int8 engine (the fp path, the fake-quant reference) silently
        serves through the ring layout — paged pages hold int8 codes.
        ``calibrated=False`` keeps the default ``ChipSpec`` even when a
        ``--chip-table`` is loaded — reference engines budget with the
        stock envelope, so the smoke's token-identity gate doubles as the
        calibrated-vs-default agreement check. ``speculate`` is opt-in per
        engine (default 0): only the measured spec engine drafts — the
        reference engines it gates against must stay token-at-a-time.
        ``record_logits`` keeps each request's logits for the kernel-route
        drift check."""
        kv = self.session_kv if kv_quant is None else kv_quant
        lay = self.kv_layout if layout is None else layout
        if kv != "int8":
            lay = "ring"
        ecfg = EngineConfig(
            slots=self.slots, cache_len=self.resolved_cache_len,
            policy=schedule or self.schedule, kv_quant=kv, kv_layout=lay,
            page_size=self.page_size, bucket_prompts=self.bucket,
            speculate=speculate, record_logits=record_logits)
        if calibrated and self.chip is not None:
            ecfg = dataclasses.replace(ecfg, chip=self.chip)
        return ecfg


def build_requests(data, n, prompt_len, gen, *, stagger=False, arrive_every=0,
                   share_prefix=0):
    """A deterministic request set from the synthetic corpus. ``stagger``
    varies prompt/generation lengths across requests (the workload shape
    continuous batching wins on); ``arrive_every`` spaces arrivals out by
    that many engine iterations; ``share_prefix`` overwrites the first that
    many tokens of every prompt with request 0's (the shared-system-prompt
    workload the paged KV layout's prefix reuse wins on)."""
    reqs = []
    base = None
    for i in range(n):
        p = prompt_len
        g = gen
        if stagger:
            p = max(4, prompt_len - 3 * (i % 4))
            g = max(2, gen - 2 * (i % 3))
        toks = data.batch(i, 1, p)["tokens"][0]
        if share_prefix:
            toks = np.asarray(toks).copy()
            if base is None:
                base = toks[:share_prefix].copy()
            k = min(share_prefix, len(toks))
            toks[:k] = base[:k]
        reqs.append(
            Request(rid=i, tokens=toks, max_new=g, arrival=i * arrive_every)
        )
    return reqs


def load_chip_table(path: str):
    """``--chip-table`` loader: a measured device-table json ->
    calibrated ``ChipSpec``. Accepts the bench JSON written by
    ``benchmarks/roofline_calibration.py`` (nested ``device_table`` key)
    or a bare table stanza."""
    import json

    from repro.dist import roofline

    with open(path) as f:
        table = json.load(f)
    if "device_table" in table:
        table = table["device_table"]
    try:
        return roofline.chip_from_table(table)
    except ValueError as e:
        raise SystemExit(f"--chip-table {path}: {e}")


def run_engine(params, cfg, bits, ctx, reqs, *, scfg: ServeConfig, schedule,
               eng=None, axes=NO_AXES, calibrated=True, on_step=None):
    """Run one request set; pass ``eng`` to reuse its compiled functions
    (reset under the new schedule instead of paying a full re-jit)."""
    if eng is None:
        ecfg = scfg.engine_config(kv_quant="none", schedule=schedule,
                                  calibrated=calibrated)
        eng = DecodeEngine(params, cfg, bits, ctx, axes, ecfg)
    else:
        eng.reset(schedule)
    if on_step is not None:
        eng.on_step = on_step
    eng.submit_all(reqs)
    completions = eng.run()
    return eng, completions


def print_stats(label, eng):
    """THE stats report: one table per serving epoch, rendered straight
    from the ``EngineStats.as_dict()`` snapshot (counters, timers and the
    TTFT / inter-token latency percentiles all come from the same metrics
    registry — no ad-hoc side channels)."""
    s = eng.stats
    d = s.as_dict()
    print(
        f"{label}: {s.completed} done | decode {s.decode_steps} steps "
        f"({s.decode_tokens_per_s:.0f} tok/s) | "
        f"prefill chunk {eng.prefill_chunk}"
    )
    width = max(len(k) for k in d)
    for k in sorted(d):
        v = d[k]
        num = f"{v:.3f}" if isinstance(v, float) else str(v)
        print(f"  {k:<{width}}  {num}")
    for a in eng.monitor.alerts:
        print(f"  ALERT[{a.severity}] {a.name}: {a.metric} {a.op} "
              f"{a.threshold:g} (value {a.value:g})")


def export_obs(args, eng):
    """``--trace-out`` / ``--metrics-out`` artifacts from one engine epoch
    (call before a ``reset()`` starts the next epoch)."""
    import os

    def ensure_dir(path):
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)

    if getattr(args, "trace_out", None):
        if eng.trace is None:
            raise SystemExit("--trace-out: engine tracing is disabled")
        ensure_dir(args.trace_out)
        eng.trace.write(args.trace_out)
        print(f"trace: {len(eng.trace.events)} events -> {args.trace_out}")
    if getattr(args, "metrics_out", None):
        import json
        ensure_dir(args.metrics_out)
        with open(args.metrics_out, "w") as f:
            json.dump(eng.metrics.snapshot(), f, indent=1, sort_keys=True)
        print(f"metrics: {len(eng.metrics)} series -> {args.metrics_out}")


def make_streamer(args):
    """``--metrics-stream``: build the JSONL snapshot streamer (or None).
    Hook it onto an engine with ``eng.on_step = streamer.tick`` — the
    engine calls it once per scheduler iteration."""
    path = getattr(args, "metrics_stream", None)
    if not path:
        return None
    import os

    from repro.obs.export import MetricsStreamer

    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    return MetricsStreamer(path,
                           interval_s=float(args.metrics_interval))


def attach_stream(args, eng):
    """Build + hook a streamer on one engine (the --policy path)."""
    streamer = make_streamer(args)
    if streamer is not None:
        eng.on_step = streamer.tick
    return streamer


def finish_stream(args, eng, streamer):
    """Close the JSONL stream (force-emitting a final snapshot, so every
    run yields >= 2 snapshots) and drop a Prometheus text dump of the
    same registry next to it (``<path>.prom``)."""
    if streamer is None:
        return
    from repro.obs.export import write_prometheus

    streamer.close(eng.metrics)
    prom = args.metrics_stream + ".prom"
    text = write_prometheus(eng.metrics, prom)
    print(f"metrics stream: {streamer.seq} snapshots -> "
          f"{args.metrics_stream} | {len(text.splitlines())} prometheus "
          f"lines -> {prom}")


def explain_policy(args, cfg):
    """``--explain-policy``: render the ILP audit trail of ``--policy``
    as a per-layer table (importance, chosen bits, bytes, binding
    constraint) and exit. The report comes from the policy's embedded
    ``SolveReport`` (``core.search.search_policy`` and
    ``demo_mixed_policy`` both embed one; serving bundles carry it in
    ``meta["solve_report"]``); a policy without one gets a descriptive
    report rebuilt from the bit assignment (zero importance, measured
    costs). A PATH argument also writes the report JSON there — the CI
    artifact."""
    from repro.core import ilp

    policy = MPQPolicy.load(args.policy)
    raw = (policy.meta or {}).get("solve_report")
    if raw is not None:
        report = ilp.SolveReport.from_json(raw)
    else:
        ql = lm.enumerate_qlayers(cfg)
        try:
            policy.validate(ql)
        except ValueError as e:
            raise SystemExit(
                f"--explain-policy: {args.policy} has no embedded "
                f"solve_report and does not match arch {cfg.name!r} "
                f"(did you mix --smoke and full variants?): {e}")
        report = ilp.describe_policy_report(
            ql, policy, sorted(int(b) for b in cfg.bits),
            meta={"arch": cfg.name, "policy_path": args.policy})
    print(report.render_table())
    if args.explain_policy != "-":
        import os
        d = os.path.dirname(args.explain_policy)
        if d:
            os.makedirs(d, exist_ok=True)
        report.save(args.explain_policy)
        print(f"solve report -> {args.explain_policy}")
    return report


def check_trace(eng, label):
    """Smoke gate: the recorded lifecycle trace and the stats counters must
    describe the same run (``repro.obs.trace.reconcile``)."""
    from repro.obs import trace as obs_trace
    if eng.trace is None:
        return
    problems = obs_trace.reconcile(eng.trace, eng.stats.as_dict())
    if problems:
        raise SystemExit(f"{label}: trace/stats reconcile failed: "
                         + "; ".join(problems))
    print(f"{label}: trace reconciles with engine stats "
          f"({len(eng.trace.events)} events)")


def calibration_report(eng, cfg, *, gate=False):
    """Replay the epoch's measured phase timings against the roofline
    step-cost model the engine budgeted with (``repro.obs.calibrate``)."""
    from repro.obs import calibrate
    report = calibrate.calibrate(
        cfg, eng.stats.as_dict(), slots=eng.ecfg.slots,
        cache_tokens=eng.ecfg.cache_len, kv_bits=eng.kv_bits,
        kv_attend=eng.kv_attend,
        w_bits_total=getattr(eng.adapter, "w_bits_total", None),
        chip=eng.ecfg.chip)
    print("roofline calibration (measured vs modeled):")
    print(calibrate.render_table(report["rows"]))
    t = report["device_table"]
    print(f"  measured device table: hbm_bytes_s={t['hbm_bytes_s']:.3e} "
          f"peak_flops={t['peak_flops']:.3e} ({t['name']})")
    # publish the worst modeled-vs-measured factor so the drift watcher
    # (obs.monitor.roofline_drift_watcher) can trip on it; the gauge only
    # exists once a calibration ran, so non-calibrating runs never alert
    from repro.obs import health as obs_health
    drift = obs_health.roofline_drift(report["rows"])
    eng.metrics.gauge(
        "roofline.drift_max",
        help="worst modeled-vs-measured phase cost factor").set(drift)
    eng.monitor.check(eng.metrics, eng.trace)
    if gate and not report["finite"]:
        raise SystemExit("roofline calibration produced a non-finite or "
                         f"non-positive ratio: {report['rows']}")
    return report


def demo_mixed_policy(cfg, meta=None):
    """A mixed MPQPolicy cycling the searched widths over the arch's QLayer
    table — a deterministic stand-in for an ILP search result. The serve
    ``--policy`` smoke and ``benchmarks/quant_serve_bench.py`` (whose
    checked-in baseline pins the exact bit assignment) must share this one
    builder."""
    from repro.core import ilp

    ql = lm.enumerate_qlayers(cfg)
    bits = sorted(int(b) for b in cfg.bits)
    n = len(bits)
    policy = MPQPolicy(
        {q.name: bits[i % n] for i, q in enumerate(ql)},
        {q.name: bits[(i + 1) % n] for i, q in enumerate(ql)},
        meta=dict(meta or {}, kind="demo-mixed", arch=cfg.name))
    # embed a descriptive SolveReport (zero importance, real costs) so
    # --write-demo-policy + --explain-policy renders without a search
    report = ilp.describe_policy_report(ql, policy, bits,
                                        meta={"kind": "demo-mixed",
                                              "arch": cfg.name})
    policy.meta["solve_report"] = report.to_json()
    return policy


def write_demo_policy(path, arch="limpq-demo", smoke=True):
    """Write a ``demo_mixed_policy`` json so the ``--policy`` serving path
    can be exercised without running the search."""
    cfg = smoke_config(arch) if smoke else get_config(arch)
    policy = demo_mixed_policy(cfg, meta={"smoke": smoke})
    policy.save(path)
    print(f"wrote demo policy for {cfg.name} ({len(policy.w_bits)} layers) "
          f"-> {path}")
    return policy


def resolve_axes(args, cfg):
    """``--mesh`` -> (MeshAxes, label). NO_AXES when no mesh requested.
    ``shard_seq=False``: serving smokes gate exact token identity against
    the single-device path."""
    if not args.mesh:
        return NO_AXES, None
    from repro.dist import sharding
    from repro.launch.mesh import make_mesh_by_name

    try:
        mesh, label = make_mesh_by_name(args.mesh)
    except ValueError as e:
        raise SystemExit(
            f"--mesh {args.mesh}: {e}. A multi-device host mesh needs "
            "XLA_FLAGS=--xla_force_host_platform_device_count=<n> set "
            "before jax initializes.")
    return sharding.make_axes_for(cfg, mesh, shard_seq=False), label


def serve_elastic(args, scfg: ServeConfig, cfg, params, ctx, reqs):
    """The ``--elastic`` path: variant bank + admission-time ILP re-solve.

    Builds an ``ElasticSession`` holding one pre-packed tree per
    ``--policy-variants`` budget (all searched over the same indicator
    banks, family-stamped against this checkpoint), hands the engine an
    ``ElasticController``, and serves the request ramp. Under ``--smoke``
    three things are gated hard: (1) the ramp must trigger at least one
    DOWNSHIFT swap (the engine degrades precision instead of queueing),
    (2) every admission-time re-solve must close under 50 ms (the paper's
    ~0.06 s claim, load-bearing on the hot path), and (3) each
    completion's tokens must be bitwise identical to its generating
    variant's offline single-policy reference — a swap may change WHO
    serves the next request, never WHAT an admitted request decodes."""
    from repro.launch import elastic as elastic_mod
    from repro.runtime.session import ElasticSession, bank_fingerprint

    base = MPQPolicy.load(scfg.policy_path)
    ql = lm.enumerate_qlayers(cfg)
    try:
        base.validate(ql, bits=cfg.bits)
        bank = elastic_mod.build_variant_bank(
            ql, cfg.bits, scfg.variant_budgets,
            family=bank_fingerprint(params))
        sess = ElasticSession(cfg, params, bank.policies, ctx,
                              kv_quant=scfg.session_kv, active=bank.full)
    except ValueError as e:
        raise SystemExit(f"--elastic: {e}")
    ctrl = elastic_mod.ElasticController(
        cfg, bank, slots=scfg.slots, cache_len=scfg.resolved_cache_len,
        chip=scfg.chip)
    eng = DecodeEngine(sess.params, cfg, None, ctx, NO_AXES,
                       scfg.engine_config(), adapter=sess, elastic=ctrl)
    streamer = attach_stream(args, eng)
    eng.submit_all(reqs)
    completions = eng.run()
    print_stats(f"elastic/{args.schedule}", eng)
    export_obs(args, eng)
    st = eng.stats
    per_variant = {}
    for c in completions.values():
        per_variant.setdefault(c.policy_id, []).append(c.rid)
    budgets = ",".join(f"{b:g}" for b in scfg.variant_budgets)
    print(f"elastic bank [{budgets}] avg-bit budgets | {st.policy_swaps} "
          f"swap(s), {st.policy_swaps_down} down | {st.ilp_solves} "
          f"admission re-solves, max {ctrl.max_solve_ms:.1f} ms | held "
          f"{st.admissions_deferred_swap} round(s) for drains | final "
          f"variant {st.active_policy}")
    for pid in sorted(per_variant):
        print(f"  {pid}: {len(per_variant[pid])} request(s) "
              f"{sorted(per_variant[pid])}")
    if args.check:
        check_trace(eng, "elastic")
        if st.policy_swaps_down < 1:
            raise SystemExit(
                "elastic smoke: the traffic ramp triggered no downshift "
                "swap — the controller never traded precision for load")
        if ctrl.max_solve_ms >= 50.0:
            raise SystemExit(
                f"elastic smoke: admission-time ILP re-solve took "
                f"{ctrl.max_solve_ms:.1f} ms (>= 50 ms budget; the paper's "
                "~0.06 s one-shot search claim is load-bearing here)")
        for pid, rids in sorted(per_variant.items()):
            vbits = lm.bits_from_policy(cfg, bank.policies[pid])
            ref = DecodeEngine(
                params, cfg, vbits, ctx, NO_AXES,
                scfg.engine_config(
                    kv_quant="fake" if scfg.session_kv == "int8" else "none",
                    calibrated=False))
            ref.submit_all([r for r in reqs if r.rid in set(rids)])
            ref_out = ref.run()
            bad = [rid for rid in rids
                   if ref_out[rid].tokens != completions[rid].tokens]
            if bad:
                raise SystemExit(
                    f"elastic variant {pid} diverged from its single-policy "
                    f"reference on rids {bad}")
        print(f"per-variant tokens identical with each generating "
              f"variant's single-policy reference ({len(completions)} "
              f"requests across {len(per_variant)} variant(s))")
    finish_stream(args, eng, streamer)
    return eng, completions


def serve_quantized(args, scfg: ServeConfig, cfg, params, ctx, reqs,
                    axes=NO_AXES):
    """The ``--policy`` path: pack a searched policy into a
    ``QuantizedSession`` and serve it through the engine. With --smoke,
    gate token identity vs the fake-quant reference graph and packed HBM
    bytes vs the policy's accounting — plus, under a tensor-parallel
    ``--mesh``, per-shard packed bytes vs the per-chip budget
    ``policy.size_bytes / tp``. ``--kv-layout paged`` serves the same
    session over pooled KV pages with shared-prefix remapping; the token
    gate then proves the paged layout against the ring reference.
    ``--speculate k`` swaps in a ``SpecSession`` (the same packed weights
    carrying a second, low-bit draft policy) and the engine decodes in
    draft-k/verify-once rounds; the smoke then adds a second token gate
    against the same session decoding token-at-a-time."""
    from repro.runtime.session import (QuantizedSession, SpecSession,
                                       summarize)

    policy = MPQPolicy.load(scfg.policy_path)
    kv = scfg.session_kv
    if scfg.speculate:
        try:
            sess = SpecSession(cfg, params, policy, ctx, axes, mode="packed",
                               kv_quant=kv, draft_w_bits=scfg.draft_bits)
        except ValueError as e:
            raise SystemExit(f"--speculate --draft-bits {scfg.draft_bits}: "
                             f"{e}")
    else:
        sess = QuantizedSession(cfg, params, policy, ctx, axes, mode="packed",
                                kv_quant=kv)
    eng = DecodeEngine(sess.params, cfg, None, ctx, axes,
                       scfg.engine_config(speculate=scfg.speculate,
                                          record_logits=bool(
                                              args.check
                                              and not scfg.speculate)),
                       adapter=sess)
    streamer = attach_stream(args, eng)
    eng.submit_all(reqs)
    completions = eng.run()
    # counters (prefill shapes compiled, act quantizes reused, routes, ...)
    # all live in the stats table now — only the HBM accounting, which is
    # session- not engine-scoped, keeps its own line
    print_stats(f"quantized/{args.schedule}", eng)
    export_obs(args, eng)
    if args.check:
        check_trace(eng, "quantized")
        calibration_report(eng, cfg, gate=True)
    # close AFTER the calibration gauge lands, so the final snapshot and
    # the prometheus dump carry the full signal plane
    finish_stream(args, eng, streamer)
    s = summarize(sess)
    print(f"packed weights: {s['packed_bytes']} B "
          f"(+{s['scale_bytes']} B scales) vs policy accounting "
          f"{s['policy_bytes']:.0f} B (x{s['packed_vs_policy']:.3f}) | "
          f"{s['compression_vs_fp32']:.2f}x smaller than fp32 | "
          f"kv={s['kv_quant']} layout={eng.ecfg.kv_layout} "
          f"decode-attn={eng.decode_attn_route}")
    print("routes (per trace): " + " ".join(
        f"{k[len('dispatch.'):]}={v:g}"
        for k, v in route_counts(eng.metrics).items()))
    scored = completions
    if scfg.speculate:
        es = eng.stats
        print(f"speculate k={scfg.speculate} draft_bits={scfg.draft_bits}: "
              f"{es.spec_rounds} rounds | drafted {es.spec_draft_tokens} "
              f"accepted {es.spec_accepted_tokens} "
              f"(accept rate {es.spec_accept_rate:.2f}) | draft pack "
              f"{sess.draft_bytes()} B on top of {s['packed_bytes']} B")
        if args.check:
            # the speculative gate proper: the SAME packed session through
            # a token-at-a-time engine — speculation must change nothing
            # but the step count (greedy acceptance is exact by
            # construction; this catches rollback/verify divergence)
            ns = DecodeEngine(sess.params, cfg, None, ctx, axes,
                              scfg.engine_config(record_logits=True),
                              adapter=sess)
            ns.submit_all(reqs)
            ns_out = ns.run()
            # the kernel-route drift check reads this engine's logits
            scored = ns_out
            bad = [r.rid for r in completions.values()
                   if ns_out[r.rid].tokens != r.tokens]
            if bad:
                raise SystemExit(
                    "speculative decode diverged from non-speculative "
                    f"packed decode: rids {bad}")
            print(f"speculative tokens identical with non-speculative "
                  f"packed decode ({eng.stats.decode_steps} spec rounds vs "
                  f"{ns.stats.decode_steps} decode steps)")
    if eng.ecfg.kv_layout == "paged":
        es = eng.stats
        print(f"paged KV: {eng.pool.n_pages} pages x "
              f"{eng.ecfg.page_size} tokens | prefix hits saved "
              f"{es.prefill_flops_saved:.0f} prefill FLOPs | "
              f"{es.prefill_compiles} prefill compile shape(s)")
    if axes.enabled and axes.tp_size > 1:
        ideal = policy.size_bytes(sess.qlayers, per_shard=axes.tp_size)
        # the gate budget follows the session's actual shard plan: a
        # projection the partition rules legitimately replicate (heads not
        # dividing the axis, etc.) counts in full per chip, so only
        # packing failures — codes replicating where the plan shards —
        # can trip it
        budget = sess.per_shard_policy_bytes()
        print(f"per-shard packed bytes: {s['per_shard_bytes']} B on each of "
              f"{axes.tp_size} tp shards vs per-chip plan budget "
              f"{budget:.0f} B (all-shardable ideal: size_bytes/tp = "
              f"{ideal:.0f} B)")
        if args.check and s["per_shard_bytes"] > budget * 1.05:
            raise SystemExit(
                f"per-shard packed bytes {s['per_shard_bytes']} exceed the "
                f"per-chip plan budget {budget:.0f} by more than padding "
                "(5%) — codes are replicating where the shard plan says "
                "they shard")
        if args.check:
            # device truth, not pack-time metadata: every codes leaf the
            # plan shards must actually BE sharded on the engine's placed
            # params (catches spec-tree / placement regressions that the
            # byte accounting above cannot see)
            from repro.runtime import packing
            bad = [pl.shape for pl in packing.packed_leaves(eng.params)
                   if pl.shard_count > 1
                   and pl.codes.sharding.is_fully_replicated]
            if bad:
                raise SystemExit(
                    f"codes replicated on-device for plan-sharded "
                    f"projections {bad[:3]} (+{max(len(bad) - 3, 0)} more)")
            print(f"on-device shardings verified: no plan-sharded codes "
                  f"leaf replicates ({len(packing.packed_leaves(eng.params))}"
                  " packed leaves)")

    if args.check or args.compare:
        # reference: the fake-quant training graph (scanned body) through
        # the same engine; int8 slots reference as quantize-dequantize fp
        bits = lm.bits_from_policy(cfg, policy)
        kernels = kernel_routes(eng.metrics)
        if args.check and (kernels or eng.decode_attn_route != "dequant-fp"):
            # op by op first: cheap, and free of the cascade that blurs the
            # end-to-end drift below
            from repro.runtime import parity
            ops = parity.kernel_parity(
                sess, ctx, layout=eng.ecfg.kv_layout, slots=scfg.slots,
                cap=scfg.resolved_cache_len, page_size=scfg.page_size,
                verify_len=scfg.speculate + 1 if scfg.speculate else 0)
            print("kernel routes vs dequant-fp, op by op (max |d| / max |ref|"
                  "): " + " ".join(f"{k}={v:.3g}" for k, v in ops.items()))
            bad = {k: v for k, v in ops.items()
                   if not v <= parity.PARITY_BOUND}
            if bad:
                raise SystemExit(
                    f"kernel routes disagree with dequant-fp beyond "
                    f"{parity.PARITY_BOUND}: {bad}")
            print(f"{len(ops)} kernel ops within {parity.PARITY_BOUND} of "
                  "dequant-fp")
        fp_out = completions
        if kernels or tie_bound() > 0:
            # the measured run took the int32 MXU matmul kernels, which are
            # exact but not bitwise equal to an fp einsum, or it ran where
            # fp graphs round apart: the identity gate serves the same
            # session, on the same layout, again with every route forced to
            # dequant-fp, and the measured run is held to a logit bound
            with dispatch.force_impl("dequant-fp"), \
                    dispatch.force_decode_attn("dequant-fp"):
                fp_eng = DecodeEngine(sess.params, cfg, None, ctx, axes,
                                      scfg.engine_config(record_logits=True),
                                      adapter=sess)
                fp_eng.submit_all(reqs)
                fp_out = fp_eng.run()
        # calibrated=False: the reference budgets with the default chip,
        # so this token gate is ALSO the calibrated-vs-default agreement
        # check when a --chip-table is loaded
        ref_ecfg = scfg.engine_config(
            kv_quant="fake" if kv == "int8" else "none", calibrated=False)
        ref = DecodeEngine(params, cfg, bits, ctx, NO_AXES, ref_ecfg)
        ref.submit_all(reqs)
        ref_out = ref.run()
        from repro.launch.engine import LMAdapter
        scorer = RefScorer(LMAdapter(cfg, bits, dataclasses.replace(
            ctx, kv_quant="fake" if kv == "int8" else "none")), params,
            scfg.resolved_cache_len)
        mismatch = [r.rid for r in fp_out.values()
                    if ref_out[r.rid].tokens != r.tokens]
        if mismatch and not tie_bound():
            raise SystemExit("packed runtime diverged from the fake-quant "
                             f"reference graph: rids {mismatch}")
        if mismatch:
            # the reference, stepped along the dequant-fp tokens, must rank
            # each of them first or within the tie bound of its first
            tf = scorer.drift(reqs, fp_out)
            print(f"dequant-fp tokens differ from the reference engine's on "
                  f"rids {mismatch}; reference teacher-forced along them: "
                  f"{tf['ties']} near-tie step(s), largest margin "
                  f"{tf['margin']:.3g} of the reference logit range "
                  f"{tf['scale']:.4g} (tie bound {tie_bound()}) | max "
                  f"|dlogit| {tf['max']:.4g} = {tf['ratio']:.3g} of the "
                  "range")
            if not (tf["margin"] <= tie_bound()
                    and tf["ratio"] <= DRIFT_BOUND):
                raise SystemExit(
                    "packed runtime diverged from the fake-quant reference "
                    f"graph beyond a near-tie: rids {mismatch}, margin "
                    f"{tf['margin']:.3g} (bound {tie_bound()}), drift "
                    f"{tf['ratio']:.3g} (bound {DRIFT_BOUND})")
            print("greedy tokens identical with the fake-quant reference "
                  f"graph but for near-ties ({len(fp_out)} requests, routes "
                  "forced to dequant-fp)")
        else:
            forced = "forced to " if fp_out is not completions else ""
            print("greedy tokens identical with the fake-quant reference "
                  f"graph ({len(fp_out)} requests, routes {forced}"
                  "dequant-fp)")
        if scfg.chip is not None:
            print(f"chip-table {scfg.chip_table}: calibrated prefill chunk "
                  f"{eng.prefill_chunk} vs default {ref.prefill_chunk} — "
                  "tokens identical, only the budget differs")
        ratio = s["packed_vs_policy"]
        if args.check and abs(ratio - 1.0) > 0.05:
            raise SystemExit(
                f"packed HBM bytes {s['packed_bytes']} off policy "
                f"accounting {s['policy_bytes']:.0f} by more than 5% "
                f"(x{ratio:.3f})")
        if args.check:
            print(f"packed HBM bytes within 5% of MPQPolicy.size_bytes "
                  f"(x{ratio:.3f})")
        if args.check and kernels:
            drift = scorer.drift(reqs, scored)
            print("kernel routes vs f32 reference (matmul precision "
                  f"'highest'): first divergent token position "
                  f"{drift['first_divergence']} | max |dlogit| per step "
                  + " ".join(f"{x:.3g}" for x in drift["per_step"])
                  + f" | max {drift['max']:.4g} = {drift['ratio']:.3g} "
                  f"of the reference logit range {drift['scale']:.4g}")
            if not drift["ratio"] <= DRIFT_BOUND:
                raise SystemExit(
                    f"kernel-route logits drift {drift['ratio']:.3g} of "
                    f"the reference logit range, above the {DRIFT_BOUND} "
                    "bound")
            print(f"kernel-route logit drift within {DRIFT_BOUND} of the "
                  "reference logit range")
    return eng, completions


# Largest kernel-route logit drift from the f32 reference, as a fraction of
# the reference's largest |logit|. int32 accumulation rounds where f32 does
# not, so an activation can land on the neighbouring code, and with random
# weights such flips cascade through every later layer: 0.096 at 28 layers
# of the smoke width on the CPU (kernels interpreted), 0.106 on a v5e at
# full width. A fault planted in the decode-attention kernel reads only
# 0.145 to 0.211 on the CPU, too close to the sound readings for a bound
# between them to hold across seeds and policies, so this bound catches
# gross errors only. ``runtime.parity`` holds each kernel to its fp route
# op by op, where a sound kernel reads ~1e-7 and those faults 0.25 to 1.4.
DRIFT_BOUND = 0.25


def kernel_routes(registry) -> list:
    """The Pallas matmul routes counted in ``registry``: int32-exact, and so
    not bitwise equal to the fp einsum of the reference graph."""
    return [name for name, n in route_counts(registry).items()
            if name.startswith("dispatch.route.pallas-") and n > 0]


def route_counts(registry) -> dict:
    """Every ``dispatch.*`` route counter of one engine epoch (counts are
    per trace, not per executed step)."""
    return {name: registry.value(name)
            for name in sorted(getattr(registry, "_metrics", {}))
            if name.startswith("dispatch.") and name.count(".") == 2
            and not name.startswith("dispatch.act_reuse")}


def tie_bound() -> float:
    """Largest margin, as a fraction of the reference logit range, by which
    a dequant-fp token may trail the reference's first choice. XLA:CPU
    evaluates the packed and the fake-quant graphs bitwise alike, so there
    it is 0 and the token gate is exact identity. A TPU rounds two
    equivalent graphs apart (packed vs fake-quant weights, paged chunked vs
    one-shot prefill, flash vs one softmax): an activation then lands on
    the neighbouring code and the flip cascades, so on a v5e at Qwen3-0.6B
    width their logits differ by 0.07 to 0.16 of the range, at the default
    matmul precision and at 'highest' alike, and a token flips where the
    reference's first two logits are closer than that. The flips seen
    there trailed by 0.013 of the range, and by at most 0.051 (the drift at
    the flip bounds it)."""
    return 0.0 if jax.default_backend() == "cpu" else 0.15


class RefScorer:
    """``ref_adapter`` traced at matmul precision 'highest' and
    teacher-forced along a run's own tokens: the prefill's last-position
    logits, then one decode step per generated token but the last.
    Requests with one prompt length run as one batch; the jitted steps are
    shared by every run scored."""

    def __init__(self, ref_adapter, ref_params, cache_len):
        self.adapter, self.params = ref_adapter, ref_params
        self._prefill = jax.jit(lambda p, t: ref_adapter.prefill(
            p, {"tokens": t}, prefill_cap=cache_len))
        self._decode = jax.jit(ref_adapter.decode)

    def _run(self, group, toks):
        # toks: (n, steps) forced tokens; rows past their own length carry
        # filler whose logits are dropped
        logits, st = self._prefill(self.params, jnp.asarray(
            np.stack([r.tokens for r in group]), jnp.int32))
        st = self.adapter.state_per_slot(st)
        rows = [logits]
        plen = group[0].prompt_len
        for t in range(toks.shape[1] - 1):
            logits, st = self._decode(
                self.params, jnp.asarray(toks[:, t:t + 1]),
                jnp.full((len(group),), plen + t, jnp.int32), st)
            rows.append(logits)
        return np.asarray(jnp.stack(rows, axis=1))   # (n, steps, V)

    def drift(self, reqs, completions):
        """Drift of the logits each completion recorded (its engine ran
        with ``record_logits``) from the reference along its tokens.
        ``first_divergence`` is the earliest step at which the reference's
        argmax differs from the completion's token (None if it never
        does); ``margin`` is the largest lead of the reference's argmax
        over the completion's token, and ``ties`` counts the steps where
        it leads at all, both as fractions of the range ``scale``."""
        per_step, first, scale = None, None, 0.0
        lead, ties = 0.0, 0
        by_len = {}
        for req in reqs:
            by_len.setdefault(req.prompt_len, []).append(req)
        for group in by_len.values():
            gen = [completions[r.rid].tokens for r in group]
            steps = max(len(g) for g in gen)
            toks = np.zeros((len(group), steps), np.int32)
            for i, g in enumerate(gen):
                toks[i, :len(g)] = g
            with jax.default_matmul_precision("highest"):
                want = self._run(group, toks)
            for i, r in enumerate(group):
                n = len(gen[i])
                got = completions[r.rid].logits
                d = np.abs(got[:n] - want[i, :n]).max(axis=-1)
                if per_step is None or n > len(per_step):
                    d, per_step = (per_step if per_step is not None
                                   else np.zeros(0)), d
                per_step[:len(d)] = np.maximum(per_step[:len(d)], d)
                w = want[i, :n]
                scale = max(scale, float(np.abs(w).max()))
                gap = w.max(axis=-1) - w[np.arange(n), gen[i]]
                lead = max(lead, float(gap.max()))
                ties += int((gap > 0).sum())
                diverged = np.nonzero(w.argmax(axis=-1)
                                      != np.asarray(gen[i]))[0]
                if diverged.size:
                    first = int(diverged[0]) if first is None \
                        else min(first, int(diverged[0]))
        worst = float(per_step.max())
        scale = scale or float("nan")
        return {"per_step": per_step.tolist(), "max": worst, "scale": scale,
                "ratio": worst / scale, "first_divergence": first,
                "margin": lead / scale, "ties": ties}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="limpq-demo")
    ap.add_argument("--smoke", action="store_true",
                    help="shrink the arch to its smoke config, cap the "
                         "request set, and run the --check gates")
    ap.add_argument("--check", action="store_true",
                    help="run the smoke gates (token identity, byte "
                         "accounting, kernel-route logit drift) at the "
                         "size given, without shrinking the model")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", "--batch", type=int, default=4, dest="slots")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--cache-len", type=int, default=0, help="0 = prompt+gen")
    ap.add_argument("--schedule", default="continuous", choices=POLICIES)
    ap.add_argument("--stagger", action="store_true")
    ap.add_argument("--arrive-every", type=int, default=0)
    ap.add_argument("--compare", action="store_true",
                    help="run continuous AND fixed; check token identity")
    ap.add_argument("--policy", default=None,
                    help="MPQPolicy json path: serve it through the packed "
                         "quantized runtime (repro.runtime.session)")
    ap.add_argument("--kv", default="int8", choices=("int8", "fp"),
                    help="KV-cache storage for the --policy runtime")
    ap.add_argument("--kv-layout", default="ring",
                    choices=dispatch.ROUTES.routes("kv_layout"),
                    help="KV-cache layout for the --policy runtime: ring = "
                         "per-slot ring buffers; paged = pooled fixed-size "
                         "pages with COW shared-prefix remapping and "
                         "chunked-append prefill")
    ap.add_argument("--page-size", type=int, default=8,
                    help="tokens per KV page (--kv-layout paged)")
    ap.add_argument("--decode-attn", default="auto",
                    choices=("auto",) + dispatch.DECODE_ATTN_ROUTES,
                    help="decode-attention route over the int8 KV cache: "
                         "auto resolves fused on TPU / dequant-fp "
                         "elsewhere; fused-interpret runs the Pallas "
                         "kernel through the interpreter (CI equivalence)")
    ap.add_argument("--speculate", type=int, default=0, metavar="K",
                    help="self-speculative decoding: a low-bit draft repack "
                         "of the same packed weights proposes K tokens per "
                         "round and the searched policy verifies them in "
                         "one batched step (needs --policy; greedy tokens "
                         "stay identical by construction)")
    ap.add_argument("--draft-bits", type=int, default=2,
                    help="draft policy weight bit-width for --speculate; "
                         "must be one of the arch's searched widths so the "
                         "draft grid shares the indicator-bank scales")
    ap.add_argument("--elastic", action="store_true",
                    help="elastic precision serving: pack a bank of policy "
                         "variants (--policy-variants budgets, searched on "
                         "the same indicator banks as --policy), re-solve "
                         "the ILP at admission time against live load, and "
                         "hot-swap the active variant between batches "
                         "(device_put of a pre-packed tree — no repacking)")
    ap.add_argument("--policy-variants", default="3,4,6", metavar="BITS",
                    help="comma-separated average weight-bit budgets of the "
                         "--elastic variant bank; each must lie inside the "
                         "arch's searched bit range")
    ap.add_argument("--mesh", default=None,
                    help="serve under a device mesh: host ((1,)) | host8 "
                         "(2-way data x 4-way tensor parallel; needs "
                         "xla_force_host_platform_device_count=8)")
    ap.add_argument("--no-bucket", action="store_true",
                    help="disable prompt-length bucketing (--policy path)")
    ap.add_argument("--chip-table", default=None, metavar="JSON",
                    help="measured device table (the bench JSON written by "
                         "benchmarks/roofline_calibration.py, or a bare "
                         "device-table stanza): budget the serving engine "
                         "with the calibrated ChipSpec instead of the "
                         "default envelope")
    ap.add_argument("--explain-policy", nargs="?", const="-", default=None,
                    metavar="PATH",
                    help="render the --policy's ILP audit trail "
                         "(SolveReport: per-layer importance, chosen bits, "
                         "bytes, binding constraint) as a table and exit; "
                         "a PATH argument also writes the report json")
    ap.add_argument("--metrics-stream", default=None, metavar="PATH",
                    help="append periodic JSONL metric snapshots while "
                         "serving (one {ts, seq, metrics} object per line); "
                         "a Prometheus text dump of the final registry "
                         "lands at PATH.prom")
    ap.add_argument("--metrics-interval", type=float, default=0.5,
                    help="seconds between --metrics-stream snapshots")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the request-lifecycle trace of the measured "
                         "run: .jsonl = one event per line, anything else = "
                         "Chrome trace JSON (chrome://tracing / Perfetto)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the engine metrics-registry snapshot (json)")
    ap.add_argument("--write-demo-policy", default=None, metavar="PATH",
                    help="write a mixed demo MPQPolicy json and exit")
    ap.add_argument("--uniform-bits", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    args.check = args.check or args.smoke
    compile_cache.enable()

    if args.write_demo_policy:
        # layer names depend on the config size, so the policy must be
        # written for the same variant (--smoke or full) it will serve
        write_demo_policy(args.write_demo_policy, args.arch,
                          smoke=args.smoke)
        return

    if args.explain_policy is not None:
        if not args.policy:
            raise SystemExit("--explain-policy needs --policy <json> (the "
                             "report explains a concrete bit assignment)")
        cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
        explain_policy(args, cfg)
        return

    if args.smoke:
        if args.schedule == "fixed":
            raise SystemExit("--smoke needs a continuous schedule: its gate "
                             "compares the engine against the fixed path")
        args.compare = True
        args.stagger = True
        # the elastic smoke needs a queue deep enough to overload the
        # slots (that is what triggers a downshift swap), so its cap is
        # looser than the single-policy one
        args.requests = min(args.requests, 12 if args.elastic else 6)
        args.prompt_len = min(args.prompt_len, 16)
        args.gen = min(args.gen, 8)

    try:
        scfg = ServeConfig.from_args(args)
    except ValueError as e:
        raise SystemExit(str(e))

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.encoder_only:
        raise SystemExit(f"{cfg.name} is encoder-only: no decode step")
    rng = jax.random.PRNGKey(scfg.seed)
    params = lm.init_params(rng, cfg)
    ctx = QuantContext.make(cfg.bits, cfg.quant_act_signed,
                            compute_dtype=jnp.float32)

    data = SyntheticLM(cfg)
    # paged serving: share half the shortest prompt across requests so the
    # smoke actually exercises prefix remapping, not just the page pool
    share = (scfg.prompt_len // 2 if scfg.kv_layout == "paged" else 0)
    reqs = build_requests(data, scfg.requests, scfg.prompt_len, scfg.gen,
                          stagger=scfg.stagger,
                          arrive_every=scfg.arrive_every,
                          share_prefix=share)

    axes, mesh_label = resolve_axes(args, cfg)
    if mesh_label:
        print(f"mesh {mesh_label}: dp={axes.dp_size} tp={axes.tp_size}")

    if scfg.policy_path:
        # the force scope must cover engine build AND runs: the route is
        # resolved both at build (roofline accounting) and at trace time
        forced = None if scfg.decode_attn == "auto" else scfg.decode_attn
        with dispatch.force_decode_attn(forced):
            if scfg.elastic:
                return serve_elastic(args, scfg, cfg, params, ctx, reqs)
            return serve_quantized(args, scfg, cfg, params, ctx, reqs,
                                   axes)

    if axes.enabled and jax.default_backend() != "tpu":
        # fake-quant fp serving has no packed-codes gather, so off-TPU it
        # must not carry model-sharded intermediates either (the packed
        # session demotes internally — see dist.axes.dp_only)
        from repro.dist.axes import dp_only
        had_tp = axes.tp_size > 1
        axes = dp_only(axes)
        if had_tp:
            print("note: off-TPU fp serving keeps only data-parallel "
                  "compute; model-parallel axes demoted")

    ql = lm.enumerate_qlayers(cfg)
    policy = MPQPolicy.uniform(ql, args.uniform_bits)
    bits = lm.bits_from_policy(cfg, policy, ql)

    eng = None
    if args.compare and args.schedule != "fixed":
        # warmup pass: pay the jit compiles up front so both measured runs
        # report steady-state throughput (serve_bench does the same)
        eng, _ = run_engine(params, cfg, bits, ctx, reqs, scfg=scfg,
                            schedule=scfg.schedule, axes=axes)
    streamer = make_streamer(args)
    eng, completions = run_engine(params, cfg, bits, ctx, reqs, scfg=scfg,
                                  schedule=scfg.schedule, eng=eng, axes=axes,
                                  on_step=streamer.tick if streamer else None)
    cont_stats = eng.stats      # reset() below replaces, not mutates, this
    print_stats(args.schedule, eng)
    # obs artifacts + gates come from THIS measured epoch, before the
    # --compare reset below starts a fresh registry/trace
    export_obs(args, eng)
    if args.check:
        check_trace(eng, args.schedule)
        calibration_report(eng, cfg, gate=True)
    finish_stream(args, eng, streamer)
    r0 = completions[0]
    print(f"generated[rid=0] ({r0.prompt_len}-token prompt):", r0.tokens)

    if args.compare and args.schedule != "fixed":
        # with a --chip-table loaded, the fixed-path comparison engine is
        # built fresh on the DEFAULT chip (calibrated=False): its token
        # gate then proves the calibrated budget changed only the chunk
        # sizes, never the tokens
        fresh_default = scfg.chip is not None
        fixed, fixed_out = run_engine(params, cfg, bits, ctx, reqs, scfg=scfg,
                                      schedule="fixed",
                                      eng=None if fresh_default else eng,
                                      axes=axes, calibrated=False)
        print_stats("fixed", fixed)
        mismatch = [r.rid for r in completions.values()
                    if fixed_out[r.rid].tokens != r.tokens]
        if mismatch:
            raise SystemExit(f"token mismatch vs fixed batch: rids {mismatch}")
        saved = fixed.stats.decode_steps - cont_stats.decode_steps
        print(f"token-identical with fixed batch; {saved} decode steps saved "
              f"({cont_stats.decode_steps} vs {fixed.stats.decode_steps})")
        if fresh_default:
            print(f"chip-table {scfg.chip_table}: calibrated prefill chunk "
                  f"{eng.prefill_chunk} vs default {fixed.prefill_chunk} — "
                  "tokens identical, only the budget differs")
        if args.check and args.stagger and saved <= 0:
            raise SystemExit("continuous batching saved no decode steps on a "
                             "staggered schedule")
    elif args.compare:
        print("note: --compare has no effect with --schedule fixed "
              "(nothing to compare the fixed path against)")

    # --- int8 execution-path equivalence on one projection -----------------
    body0 = params.get("body", {}).get("0", {})
    if "wq" in body0:
        from repro.core.quantizer import bit_range
        from repro.kernels import ops
        p0 = body0["wq"]
        w = p0["w"][0] if p0["w"].ndim == 3 else p0["w"]
        s_w = (p0["s_w"][0] if p0["s_w"].ndim == 2 else p0["s_w"])[2]  # 4-bit
        qmin, qmax = bit_range(4, True)
        wq = jnp.clip(jnp.round(w / s_w), qmin, qmax).astype(jnp.int8)
        x = jax.random.normal(rng, (8, w.shape[0]), jnp.float32)
        s_x = jnp.float32(0.05)
        xq = jnp.clip(jnp.round(x / s_x), qmin, qmax).astype(jnp.int8)
        fused = ops.quant_matmul(xq, wq, s_x, s_w, blocks=(8, 128, 128))
        ref = (xq.astype(jnp.float32) * s_x) @ (wq.astype(jnp.float32) * s_w)
        err = float(jnp.max(jnp.abs(fused - ref)))
        print(f"int8 quant_matmul vs fake-quant ref: max_err={err:.2e}")


if __name__ == "__main__":
    main()
