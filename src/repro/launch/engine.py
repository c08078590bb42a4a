"""Continuous-batching decode engine (ROADMAP item 3).

The engine turns a model's prefill/decode passes into a servable system:
``slots`` concurrent sequences share one jitted decode step over per-slot
KV caches (``init_decode_state(per_slot=True)`` — the slot axis is what
``dist.sharding.decode_state_specs`` shards over ``dp``), and a
``repro.launch.scheduler.Scheduler`` decides admission. A finished
sequence frees its slot mid-flight, so a staggered workload completes in
strictly fewer decode steps than padding everything to the max length.

The model behind the engine is pluggable: a *model adapter* supplies
``prefill`` / ``decode`` / ``init_state`` / ``state_per_slot``. The default
``LMAdapter`` is the fake-quant ``repro.models.lm`` graph; the quantized
serving runtime (``repro.runtime.session.QuantizedSession``) is the
packed-weights implementation of the same interface, which is how
``serve --policy`` runs a searched ``MPQPolicy`` through this engine
unchanged.

Execution model (host loop, three jitted device functions):

* ``prefill``  — one request at a time, whole prompt, ``prefill_cap`` sized
  to the slot's cache. Recompiles per distinct prompt length (the jit cache
  keys on shape); ``EngineConfig.bucket_prompts`` rounds prompts up to
  power-of-two buckets (``scheduler.bucket_length``) so at most
  ``log2(cache_len)`` shapes ever compile — pad tokens sit after the
  prompt, logits read at the true last position, pad KV rows invalidated.
* ``insert``  — writes the prefilled per-layer state into slot row ``i``
  (``dynamic_update_slice`` on the slot axis; axis 1 for body-stacked
  segments, axis 0 elsewhere).
* ``decode``  — one token for all slots at once with a per-slot position
  vector. Free slots ride along at position -1: their row writes land with
  position -1 (never valid to attend), so an evicted slot can never leak KV
  entries into a later occupant — admission overwrites the whole row anyway.

``EngineConfig.kv_quant`` flips the per-slot KV caches to int8 codes with
per-head write-time scales (``repro.runtime.kv_cache``), halving decode
HBM traffic per cache element. How the cache is *attended* routes through
``runtime.dispatch.resolve_decode_attn`` (fused Pallas kernel on codes vs
the dequant-fp fallback); the engine resolves the route once at build
(``stats.decode_attn_route``) and the roofline-driven prefill budget
charges the matching bytes through ``decode_step_cost(kv_bits=8,
kv_attend=...)`` — "int8 stored but fp-attended" costs more than "int8
attended" and the budget reflects which one this process actually runs.

Mesh execution: when ``axes`` carries a real mesh (``dist.sharding
.make_axes_for``), the engine resolves partition specs once at build —
params through the adapter's ``param_specs()`` hook (``packed_specs`` for
a quantized session: sub-byte ``codes`` shard over ``tp`` instead of
replicating) falling back to ``dist.sharding.param_specs``, and the
per-slot decode state (fp or int8 KV) through ``decode_state_specs`` —
``device_put``s both onto the mesh, and jits prefill/insert/decode/evict
with explicit ``in_shardings``/``out_shardings``. Under ``NO_AXES`` (or a
trivial host ``(1,)`` mesh) the same code path degenerates to the
single-device behavior bit-exactly.

Inactive slots still occupy compute (the decode batch is static — standard
for continuous-batching engines); the win is scheduling, measured by
``EngineStats.decode_steps`` / ``slot_steps``.

Observability (``repro.obs``): every engine owns a
``MetricsRegistry`` (``engine.metrics``) and a ``TraceRecorder``
(``engine.trace``). Counters/gauges/histograms are the source of truth —
``engine.stats`` is a *snapshot* property that renders the registry into
an ``EngineStats`` (so a captured ``stats`` object stays frozen across
``reset()``), and ``as_dict()`` carries the TTFT / inter-token-latency
percentiles the histograms accumulate. Each request traces its lifecycle
(``admit`` → ``prefill`` span → ``first_token`` → per-decode-tick
``token`` instants → ``complete``/``evict``); phase timers use
``time.perf_counter`` and stamp only after ``jax.block_until_ready`` on
the FULL output tree (logits *and* the new cache state), so async cache
writes can never leak into the next phase's timing. ``serve
--trace-out`` exports the trace as JSONL or Chrome-trace/Perfetto.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.dist import roofline
from repro.dist.axes import NO_AXES, MeshAxes
from repro.launch.scheduler import (
    Completion,
    Request,
    Scheduler,
    bucket_length,
    prefix_chain_keys,
)
from repro.models import attention as attn
from repro.models import lm
from repro.runtime import kv_cache as qkv
from repro.obs import health as obs_health
from repro.obs import metrics as obs_metrics
from repro.obs import monitor as obs_monitor
from repro.obs import trace as obs_trace


@dataclasses.dataclass
class EngineConfig:
    """Engine knobs (see README "Serving" for the full story)."""

    slots: int = 4  # concurrent sequences
    cache_len: int = 64  # per-slot KV capacity (prompt + generation)
    prefill_chunk: int = 0  # prefill tokens per iteration; 0 = roofline auto
    policy: str = "continuous"  # continuous | continuous-sjf | fixed
    eos_id: Optional[int] = None  # optional early-stop token id
    state_dtype: Any = jnp.float32
    max_iters: int = 100_000  # hard stop for the host loop
    chip: Optional[roofline.ChipSpec] = None  # None = roofline.local_chip()
    kv_quant: str = "none"  # "none" | "int8" | "fake" (reference numerics)
    kv_layout: str = "ring"  # "ring" | "paged" (pooled pages + prefix reuse)
    page_size: int = 8  # tokens per KV page (paged layout only)
    bucket_prompts: bool = False  # pow-2 prompt padding to bound re-jits
    bucket_min: int = 8  # smallest prompt bucket
    trace: bool = True  # record the per-request lifecycle event trace
    health_every: int = 4  # KV-scale drift sample stride (decode steps; 0 off)
    speculate: int = 0  # self-speculative draft length k (0 = off)
    # keep every request's per-token logits on the host (Completion.logits):
    # the serving checks hold this engine's own logits to a reference
    record_logits: bool = False


@dataclasses.dataclass
class EngineStats:
    """A frozen-on-read snapshot of the engine's metrics registry.

    The engine never mutates an ``EngineStats`` — instrumented call sites
    write ``engine.metrics`` counters/gauges/histograms and the ``stats``
    property renders this view on access. ``latency`` carries the
    percentile summary of the TTFT / inter-token / per-phase histograms
    and is flattened into ``as_dict()``.
    """

    iterations: int = 0  # scheduler ticks (admission and/or decode)
    decode_steps: int = 0  # jitted decode launches
    slot_steps: int = 0  # sum over decode steps of slots emitting a token
    padded_slot_steps: int = 0  # sum of *occupied* slots (fixed pads to max)
    prefill_calls: int = 0
    prefill_tokens: int = 0
    prefill_compiles: int = 0  # distinct prompt shapes fed to the jit cache
    act_quant_reused: int = 0  # activation quantize ops elided per compile
    decode_attn_route: str = "fp"  # fused | fused-interpret | dequant-fp | fp
    admitted: int = 0
    completed: int = 0
    tokens_generated: int = 0
    prefill_flops_saved: float = 0.0  # MACs*2 skipped via shared-prefix pages
    prefix_hit_tokens: int = 0  # prompt tokens served by page-table remaps
    kv_unique_pages: int = 0  # paged layout: distinct physical pages mapped
    admissions_deferred_pool: int = 0  # admit rounds held on page pressure
    alerts_fired: int = 0  # monitor threshold trips this epoch
    spec_rounds: int = 0  # draft+verify rounds (speculate > 0)
    spec_draft_tokens: int = 0  # tokens the low-bit draft policy proposed
    spec_accepted_tokens: int = 0  # proposals the target policy confirmed
    policy_swaps: int = 0  # elastic variant hot-swaps applied this epoch
    policy_swaps_down: int = 0  # swaps that lowered the served avg bits
    ilp_solves: int = 0  # admission-time MCKP re-solves (elastic)
    admissions_deferred_swap: int = 0  # admit rounds held for a swap drain
    active_policy: str = ""  # serving variant id ("" = single-policy)
    t_prefill_s: float = 0.0
    t_decode_s: float = 0.0
    latency: Dict[str, float] = dataclasses.field(default_factory=dict)

    @property
    def decode_tokens_per_s(self) -> float:
        return self.tokens_generated / max(self.t_decode_s, 1e-9)

    @property
    def total_tokens_per_s(self) -> float:
        total = self.tokens_generated + self.prefill_tokens
        return total / max(self.t_decode_s + self.t_prefill_s, 1e-9)

    @property
    def spec_accept_rate(self) -> float:
        """Fraction of drafted tokens the target verified (greedy match)."""
        return self.spec_accepted_tokens / max(self.spec_draft_tokens, 1)

    def as_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d.update(d.pop("latency"))
        d["decode_tokens_per_s"] = self.decode_tokens_per_s
        d["total_tokens_per_s"] = self.total_tokens_per_s
        d["spec_accept_rate"] = self.spec_accept_rate
        return d


class LMAdapter:
    """Default model adapter: the fake-quant ``repro.models.lm`` graph.

    Anything exposing this interface (plus the optional ``kv_quant`` /
    ``w_bits_total`` accounting attributes) can serve through the engine —
    see ``repro.runtime.session.QuantizedSession`` for the packed
    mixed-precision implementation.

    Elastic serving (``DecodeEngine(elastic=...)``) needs the optional
    variant-bank extension of this seam — ``active_policy`` naming the
    serving variant plus ``set_active(pid)`` / ``params_for(pid)``
    returning pre-packed trees (``runtime.session.ElasticSession``). The
    default single-policy adapters leave ``active_policy`` empty and
    carry no bank.
    """

    active_policy = ""  # single policy per process: nothing to attribute

    def __init__(self, cfg: ModelConfig, bits, ctx, axes: MeshAxes = NO_AXES):
        self.cfg = cfg
        self.bits = bits
        self.ctx = ctx
        self.axes = axes

    @property
    def kv_quant(self) -> str:
        return self.ctx.kv_quant

    @property
    def w_bits_total(self) -> Optional[float]:
        return None  # fp/fake-quant weights: roofline uses avg_weight_bits

    def prefill(self, params, inputs, *, prefill_cap, true_len=None):
        return lm.apply_prefill(
            params,
            self.cfg,
            inputs,
            self.bits,
            self.ctx,
            self.axes,
            prefill_cap=prefill_cap,
            true_len=true_len,
        )

    def decode(self, params, tok, pos, state):
        return lm.apply_decode(
            params, self.cfg, tok, pos, state, self.bits, self.ctx, self.axes
        )

    def init_state(self, batch, capacity, dtype, per_slot=True):
        return lm.init_decode_state(
            self.cfg,
            batch,
            capacity,
            dtype=dtype,
            per_slot=per_slot,
            kv_quant="int8" if self.ctx.kv_quant == "int8" else "none",
        )

    def state_per_slot(self, row):
        return lm.decode_state_per_slot(row)


class _Slot:
    """Host-side bookkeeping for one engine slot."""

    __slots__ = (
        "req",
        "next_tok",
        "next_pos",
        "gen",
        "done",
        "admitted_at",
        "ts_admit",
        "ts_last_token",
        "spec_drafted",
        "spec_accepted",
        "policy_id",
        "logits",
    )

    def __init__(
        self,
        req: Request,
        first_tok: int,
        now: int,
        ts_admit: float = 0.0,
        ts_last_token: float = 0.0,
        policy_id: str = "",
    ):
        self.req = req
        self.next_tok = first_tok
        self.next_pos = req.prompt_len
        self.gen: List[int] = [first_tok]
        self.done = False
        self.admitted_at = now
        self.ts_admit = ts_admit  # trace-clock stamp of the admit event
        self.ts_last_token = ts_last_token  # last emitted token (ITL base)
        self.spec_drafted = 0  # draft proposals made for this slot
        self.spec_accepted = 0  # proposals the target policy confirmed
        # elastic serving: the variant that admitted this request keeps
        # serving it to completion (drain-then-swap), so one id covers
        # every token
        self.policy_id = policy_id
        self.logits: Optional[List[np.ndarray]] = None  # record_logits rows


class DecodeEngine:
    """Slot-based continuous-batching decode engine over a quantized LM."""

    def __init__(
        self,
        params,
        cfg: ModelConfig,
        bits,
        ctx,
        axes: MeshAxes = NO_AXES,
        ecfg: Optional[EngineConfig] = None,
        scheduler: Optional[Scheduler] = None,
        adapter=None,
        elastic=None,
    ):
        if cfg.encoder_only:
            raise ValueError(f"{cfg.name} is encoder-only: no decode step")
        self.params = params
        self.cfg = cfg
        self.ecfg = ecfg or EngineConfig()
        if self.ecfg.chip is None:
            self.ecfg = dataclasses.replace(self.ecfg, chip=roofline.local_chip())
        if adapter is None:
            if self.ecfg.kv_quant != "none" and ctx.kv_quant == "none":
                ctx = dataclasses.replace(ctx, kv_quant=self.ecfg.kv_quant)
            adapter = LMAdapter(cfg, bits, ctx, axes)
        self.adapter = adapter

        kv_mode = getattr(adapter, "kv_quant", self.ecfg.kv_quant)
        from repro.runtime import dispatch as _dispatch

        _dispatch.ROUTES.validate("kv_layout", self.ecfg.kv_layout)
        self._paged = self.ecfg.kv_layout == "paged"
        self.layout: Optional[qkv.KVCacheLayout] = None
        self.pool: Optional[qkv.PagePool] = None
        if self._paged:
            # the paged layout is the packed int8 serving path: pooled int8
            # pages, a slot -> page-list table, and chunked append prefill
            if kv_mode != "int8":
                raise ValueError(
                    f"kv_layout='paged' requires int8 KV (got {kv_mode!r}):"
                    " pages hold codes + scales"
                )
            if not hasattr(adapter, "append"):
                raise ValueError(
                    "kv_layout='paged' needs an append-capable adapter "
                    "(QuantizedSession); the fake-quant LMAdapter serves "
                    "through the ring layout"
                )
            if cfg.sliding_window or cfg.local_window:
                raise ValueError(
                    "kv_layout='paged' does not support sliding-window "
                    "archs: a window evicts mid-page, breaking page sharing"
                )
            if axes.enabled:
                raise ValueError(
                    "kv_layout='paged' is single-device for now: the page "
                    "pool id space is not mesh-sharded"
                )
            self.layout = qkv.KVCacheLayout(
                kind="paged", quant="int8", page_size=self.ecfg.page_size
            )
            self._pages_per_slot = self.layout.pages_per_slot(
                self.ecfg.cache_len
            )
            # FLOPs one prompt token costs across every quantized matmul —
            # what a shared-prefix page-table hit avoids recomputing
            self._flops_per_token = 2.0 * sum(
                q.macs_per_token * q.n_mats for q in lm.enumerate_qlayers(cfg)
            )
        self._spec_k = int(self.ecfg.speculate or 0)
        self.draft_params = getattr(adapter, "draft_params", None)
        if self._spec_k:
            # self-speculative decoding: the adapter must carry the dual
            # pack (runtime.session.SpecSession) and the schedule must be
            # rollback-safe — rejecting a draft token rewinds KV rows by
            # position, which only attention caches support
            if not hasattr(adapter, "verify") or self.draft_params is None:
                raise ValueError(
                    "speculate > 0 needs a dual-policy adapter "
                    "(runtime.session.SpecSession): a draft_params tree to "
                    "propose tokens and a verify() pass to confirm them"
                )
            if axes.enabled:
                raise ValueError(
                    "speculate > 0 is single-device for now: the draft/"
                    "verify interleave donates one state across two jits"
                )
            bad = {s.kind for s in lm.iter_sites(cfg)} - {"attn", "dense", "moe"}
            if bad:
                raise ValueError(
                    f"speculate > 0 requires an attention-only schedule: "
                    f"{sorted(bad)} state is sequential and cannot roll "
                    "back past a rejected draft token"
                )
            if cfg.sliding_window or cfg.local_window:
                raise ValueError(
                    "speculate > 0 does not support sliding-window archs: "
                    "the ring window overwrites rows a rollback would need"
                )
            if self.ecfg.record_logits:
                raise ValueError(
                    "record_logits is token-at-a-time only: a speculative "
                    "round scores its draft tokens in one verify pass"
                )
        # elastic serving: an ElasticController re-solves the ILP at
        # admission time and this engine hot-swaps the active pre-packed
        # variant between batches (drain-then-swap; _elastic_admission)
        self.elastic = elastic
        self._active_policy = str(getattr(adapter, "active_policy", "") or "")
        self._swap_decision = None
        self._deferred_seen = 0
        if elastic is not None:
            _dispatch.ROUTES.validate("elastic", "bank")
            if not (
                hasattr(adapter, "set_active") and hasattr(adapter, "params_for")
            ):
                raise ValueError(
                    "elastic serving needs a variant-bank adapter "
                    "(runtime.session.ElasticSession): set_active()/"
                    "params_for() hand back pre-packed policy variants; a "
                    "single-policy adapter has nothing to hot-swap"
                )
            if axes.enabled:
                raise ValueError(
                    "elastic serving is single-device for now: a swap would "
                    "have to re-place every packed shard on the mesh"
                )
            if self._spec_k:
                raise ValueError(
                    "elastic + speculate is unsupported: the draft pack is "
                    "derived from ONE target policy and would go stale at "
                    "the first swap"
                )
        kv_bits = (
            8.0
            if kv_mode == "int8"
            else 8.0 * np.dtype(self.ecfg.state_dtype).itemsize
        )
        # which route decode attention takes over the int8 cache: resolved
        # once here for the roofline budget and the stats/bench trail (the
        # jitted decode resolves the same dispatch at trace time, so a
        # force_decode_attn scope must wrap build AND first run)
        if kv_mode == "int8":
            with _dispatch.axes_scope(axes):
                self.decode_attn_route = _dispatch.resolve_decode_attn()
        else:
            self.decode_attn_route = "fp"
        kv_attend = (
            "fused" if self.decode_attn_route.startswith("fused") else "dequant"
        )
        # the roofline budget shape, kept for obs.calibrate to replay the
        # measured timings against the same model the engine planned with
        self.kv_bits = float(kv_bits)
        self.kv_attend = kv_attend
        chunk = self.ecfg.prefill_chunk or roofline.suggest_prefill_chunk(
            cfg,
            self.ecfg.slots,
            cache_tokens=self.ecfg.cache_len,
            kv_bits=kv_bits,
            kv_attend=kv_attend,
            w_bits_total=getattr(adapter, "w_bits_total", None),
            # a speculating engine's iteration is a whole draft+verify
            # round, so the per-iteration prefill headroom must be
            # budgeted against the round cost, not a single-token step
            spec_k=self._spec_k,
            draft_w_bits=float(getattr(adapter, "draft_w_bits", 2.0)),
            chip=self.ecfg.chip,
        )
        self.prefill_chunk = int(chunk)
        self._init_obs()
        self.scheduler = scheduler or Scheduler(
            self.ecfg.policy, self.prefill_chunk, metrics=self.metrics
        )
        # the adapter's reuse counter is lifetime-cumulative across every
        # trace it ever ran; stats report the delta since this engine's
        # build (reset() re-snapshots), i.e. ops elided by THIS engine's
        # compiles
        self._act_reuse_base = getattr(adapter, "act_quant_reused", 0)
        self.slots: List[Optional[_Slot]] = [None] * self.ecfg.slots
        self.completions: Dict[int, Completion] = {}
        self._submitted: Dict[int, float] = {}  # rid -> submit time (TTFT)
        self.axes = axes
        self._mesh = axes.mesh if axes.enabled else None
        self._param_shardings = None
        self._state_shardings = None
        if self._mesh is not None:
            from repro.dist import sharding as shd

            spec_fn = getattr(adapter, "param_specs", None)
            pspecs = spec_fn() if spec_fn else shd.param_specs(cfg, self.params, axes)
            self._param_shardings = shd.named(self._mesh, pspecs)
            # named once at build: packed codes/scales land on their tp
            # shards, everything else on its megatron home, before any jit
            self.params = jax.device_put(self.params, self._param_shardings)
        self.state = self._fresh_state()
        self._set_cache_gauges()

        # prompt-length bucketing bounds prefill recompiles, but padded
        # prompt tokens would perturb recurrent state (rwkv/rec scans run
        # over them) and sliding-window caches (pads evict real rows), so
        # it only engages for full-attention schedules
        self._bucket = bool(self.ecfg.bucket_prompts)
        if self._paged:
            # chunked-append prefill already bounds compiles to ONE chunk
            # shape — bucketing would only pad for no benefit
            self._bucket = False
        if self._bucket:
            kinds = {s.kind for s in lm.iter_sites(cfg)}
            windowed = bool(cfg.sliding_window or cfg.local_window)
            if (kinds & {"rwkv", "rec"}) or windowed:
                self._bucket = False
        self._prefill_shapes: set = set()

        cache_len = self.ecfg.cache_len

        if self._bucket:

            def prefill(p, inputs, true_len):
                return adapter.prefill(
                    p, inputs, prefill_cap=cache_len, true_len=true_len
                )

        else:

            def prefill(p, inputs):
                return adapter.prefill(p, inputs, prefill_cap=cache_len)

        def decode(p, tok, pos, state):
            return adapter.decode(p, tok, pos, state)

        def insert(full, row, slot):
            def one(path, f, r):
                seg = str(getattr(path[0], "key", path[0]))
                axis = 1 if seg == "body" else 0
                return jax.lax.dynamic_update_slice_in_dim(
                    f, r.astype(f.dtype), slot, axis=axis
                )

            return jax.tree_util.tree_map_with_path(one, full, row)

        def evict(state, slot):
            def one(c):
                if isinstance(c, qkv.PagedKVCache):
                    return c.evict(slot)  # unmap the table row; the pool
                    # frees + pos-clears the physical pages host-side
                if not isinstance(c, attn.CACHE_TYPES):
                    return c
                axis = c.pos.ndim - 2  # slot axis: 0 plain, 1 body-stacked
                empty_shape = list(c.pos.shape)
                empty_shape[axis] = 1
                empty = jnp.full(empty_shape, -1, jnp.int32)
                pos = jax.lax.dynamic_update_slice_in_dim(
                    c.pos, empty, slot, axis=axis
                )
                return c._replace(pos=pos)

            return jax.tree.map(
                one, state, is_leaf=lambda x: isinstance(x, attn.CACHE_TYPES)
            )

        def _paged_only(fn):
            def apply(state, *args):
                return jax.tree.map(
                    lambda c: fn(c, *args)
                    if isinstance(c, qkv.PagedKVCache)
                    else c,
                    state,
                    is_leaf=lambda x: isinstance(x, attn.CACHE_TYPES),
                )

            return apply

        map_slot = _paged_only(lambda c, slot, row: c.map_slot(slot, row))
        free_pages = _paged_only(lambda c, ids: c.free_pages(ids))

        def append(p, tok, qpos, slot, last_idx, state):
            return adapter.append(p, tok, qpos, slot, last_idx, state)

        if self._mesh is None:
            self._prefill = jax.jit(prefill)
            self._decode = jax.jit(decode, donate_argnums=(3,))
            self._insert = jax.jit(insert, donate_argnums=(0,))
            self._evict = jax.jit(evict, donate_argnums=(0,))
            self._map_slot = jax.jit(map_slot, donate_argnums=(0,))
            self._free_pages = jax.jit(free_pages, donate_argnums=(0,))
            self._append = (
                jax.jit(append, donate_argnums=(5,)) if self._paged else None
            )
            self._spec_verify = jax.jit(
                self._spec_verify_fn, donate_argnums=(5,)
            )
            self._spec_draft_jits: Dict[int, Any] = {}
            self._spec_fused_jits: Dict[int, Any] = {}
        else:
            # explicit shardings end-to-end: params enter on their specs,
            # the decode state's slot axis stays pinned over dp across the
            # donate chain, and decode logits come back replicated for the
            # host-side argmax
            from jax.sharding import NamedSharding, PartitionSpec as P

            ps, ss = self._param_shardings, self._state_shardings
            rep = NamedSharding(self._mesh, P())
            pre_in = (ps, None, None) if self._bucket else (ps, None)
            self._prefill = jax.jit(prefill, in_shardings=pre_in)
            self._decode = jax.jit(
                decode,
                donate_argnums=(3,),
                in_shardings=(ps, None, None, ss),
                out_shardings=(rep, ss),
            )
            self._insert = jax.jit(
                insert,
                donate_argnums=(0,),
                in_shardings=(ss, None, None),
                out_shardings=ss,
            )
            self._evict = jax.jit(
                evict,
                donate_argnums=(0,),
                in_shardings=(ss, None),
                out_shardings=ss,
            )
            self._map_slot = self._free_pages = self._append = None
            self._spec_verify = None
            self._spec_draft_jits = {}
            self._spec_fused_jits = {}

    # -- observability -------------------------------------------------------
    def _init_obs(self) -> None:
        """Fresh metrics registry + trace recorder for one serving epoch.

        Counters are monotonic *within* an epoch; ``reset()`` starts a new
        epoch with a new registry, so any previously captured
        ``EngineStats`` snapshot (and the old registry itself) stays
        frozen instead of being rewound.
        """
        self.metrics = obs_metrics.MetricsRegistry()
        self.trace = obs_trace.TraceRecorder() if self.ecfg.trace else None
        m = self.metrics
        m.gauge(
            "engine.slots", help="configured concurrent-sequence capacity"
        ).set(self.ecfg.slots)
        m.gauge("engine.prefill_chunk").set(self.prefill_chunk)
        if self.ecfg.speculate:
            m.gauge(
                "engine.speculate", help="self-speculative draft length k"
            ).set(self.ecfg.speculate)
        # registry-side route record; the string itself stays on
        # self.decode_attn_route / EngineStats.decode_attn_route
        m.counter(f"engine.decode_attn_route.{self.decode_attn_route}").inc()
        # the adapter shares the registry so runtime.dispatch can count
        # routes chosen / activation-reuse hits at trace time
        if hasattr(self.adapter, "metrics"):
            self.adapter.metrics = self.metrics
        if hasattr(self.adapter, "packed_bytes"):
            m.gauge(
                "engine.packed_bytes", help="resident packed weight codes"
            ).set(self.adapter.packed_bytes())
        if hasattr(self.adapter, "scale_bytes"):
            m.gauge("engine.scale_bytes").set(self.adapter.scale_bytes())
        # pack-time quantization health (QuantizedSession computes it once
        # at build from the materialized weights; publishing per epoch keeps
        # every registry self-contained for snapshots/streaming)
        pack_health = getattr(self.adapter, "pack_health", None)
        if pack_health:
            obs_health.publish_pack_health(m, pack_health)
        self._kv_drift = obs_health.KVScaleDrift()
        # threshold watchers: alerts land in this registry (alerts.fired)
        # and, as `alert` instants, in the trace. The pool watcher reads
        # available pages (free + LRU-evictable) — free_count alone would
        # cry wolf whenever the prefix registry is merely full, while an
        # admission could still evict its way to a full slot's pages.
        self.monitor = obs_monitor.default_monitor(
            pool_min_free=(self._pages_per_slot - 1) if self._paged else None
        )
        # elastic epoch state: a pending (unapplied) swap decision and the
        # page-pool deferral watermark the controller diffs against
        self._swap_decision = None
        self._deferred_seen = 0
        if self.elastic is not None:
            m.gauge(
                "engine.policy_variants",
                help="pre-packed policy variants resident in the bank",
            ).set(len(self.adapter.variants))
            self._observe_active_policy()
            if self.trace is not None:
                # seed the swap-epoch timeline: reconcile validates every
                # policy-stamped token against the epoch active at its ts,
                # so epoch zero needs an explicit marker
                self.trace.instant(
                    "policy_swap",
                    to=self._active_policy,
                    initial=True,
                    iteration=-1,
                )
        # optional per-iteration callback (serve --metrics-stream); survives
        # reset() so a streamer set up once covers every epoch
        self.on_step = getattr(self, "on_step", None)

    def _set_cache_gauges(self) -> None:
        """Resident KV-cache inventory gauges (int8 caches; fp caches have
        no quantized inventory to itemize)."""
        inv = qkv.tree_inventory(self.state)
        m = self.metrics
        m.gauge(
            "engine.kv_cache_bytes", help="codes + scales + pos, all quantized caches"
        ).set(sum(inv.values()))
        for part, nbytes in inv.items():
            m.gauge(f"engine.kv_{part}_bytes").set(nbytes)
        if self._paged:
            m.gauge(
                "engine.kv_unique_pages",
                help="distinct physical pages currently referenced",
            ).set(self.pool.unique_pages_in_use)
            self._set_pool_gauges()

    def _set_pool_gauges(self) -> None:
        m = self.metrics
        m.gauge(
            "engine.kv_pool_free_pages", help="PagePool free-list length"
        ).set(self.pool.free_count)
        m.gauge(
            "engine.kv_pool_available_pages",
            help="free + LRU-evictable pages (admission headroom)",
        ).set(self.pool.available_count)

    # -- elastic precision serving ------------------------------------------
    def _observe_active_policy(self) -> None:
        m = self.metrics
        avg_w, _ = self.adapter.policy.avg_bits()
        m.gauge(
            "engine.active_policy_avg_bits",
            help="mean weight bits of the serving variant",
        ).set(avg_w)
        m.counter(f"engine.policy_active.{self._active_policy}").inc()
        # packed_bytes follows the active variant (ElasticSession accounting
        # swaps with set_active); refresh so the gauge tracks what serves
        m.gauge("engine.packed_bytes").set(self.adapter.packed_bytes())

    def _elastic_admission(self, now: int) -> None:
        """Consult the controller before admitting (drain-then-swap).

        Re-solves EVERY admission round with pending work — the decision
        self-corrects while slots drain, and the per-solve cost is the
        tens-of-ms the ``ilp.solve_ms`` histogram polices. A decision for
        a different variant swaps immediately if the slots are empty;
        otherwise it parks in ``_swap_decision``, which holds admission
        (``Scheduler.admit(hold=True)``) until the in-flight requests
        finish under the variant that admitted them. Decode itself never
        pauses, so the drain cannot deadlock."""
        m = self.metrics
        deferred_now = int(m.value("scheduler.admissions_deferred_pool"))
        arrived = sum(1 for r in self.scheduler.pending if r.arrival <= now)
        decision = self.elastic.decide(
            active=self._active_policy,
            queue_depth=arrived,
            occupied=len(self._occupied()),
            slots=self.ecfg.slots,
            deferred=max(deferred_now - self._deferred_seen, 0),
            cache_bytes=float(sum(qkv.tree_inventory(self.state).values())),
        )
        self._deferred_seen = deferred_now
        m.histogram(
            "ilp.solve_ms", help="admission-time MCKP re-solve wall time"
        ).observe(decision.solve_ms)
        m.counter("engine.ilp_solves").inc()
        if decision.target == self._active_policy:
            self._swap_decision = None
            return
        self._swap_decision = decision
        if not self._occupied():
            self._apply_swap(decision, now)

    def _apply_swap(self, decision, now: int) -> None:
        """Hot-swap the serving variant: ``device_put`` of the adapter's
        PRE-PACKED tree — never a repack. Runs only on drained slots, so
        every request's tokens come from exactly one variant."""
        assert not self._occupied(), "policy swap with occupied slots"
        t0 = time.perf_counter()
        self.params = jax.device_put(self.adapter.set_active(decision.target))
        jax.block_until_ready(self.params)
        dt = time.perf_counter() - t0
        prev, self._active_policy = self._active_policy, decision.target
        self._swap_decision = None
        m = self.metrics
        if self._paged:
            # registered prefix pages hold KV computed under the previous
            # variant's weights; a post-swap prefix hit would splice stale
            # numerics into a request that must match its own variant's
            # single-policy reference bit-for-bit
            self._clear_freed(self.pool.flush_prefixes())
            m.gauge("engine.kv_unique_pages").set(self.pool.unique_pages_in_use)
            self._set_pool_gauges()
        pols = self.adapter.variant_policies
        down = pols[decision.target].avg_bits()[0] < pols[prev].avg_bits()[0]
        m.counter("engine.policy_swaps").inc()
        m.counter(
            "engine.policy_swaps_down" if down else "engine.policy_swaps_up"
        ).inc()
        m.histogram("engine.swap_ms").observe(dt * 1e3)
        self._observe_active_policy()
        if self.trace is not None:
            self.trace.instant(
                "policy_swap",
                ts=self.trace.now(),
                to=decision.target,
                from_policy=prev,
                budget_bits=decision.budget_bits,
                solver=decision.solver,
                solve_ms=decision.solve_ms,
                report=decision.summary(),
                iteration=now,
            )

    @property
    def stats(self) -> EngineStats:
        """Render the metrics registry into a frozen ``EngineStats``
        snapshot (see the dataclass docstring)."""
        m = self.metrics

        def c(name: str) -> int:
            return int(m.value(f"engine.{name}"))

        lat: Dict[str, float] = {}
        for key in ("ttft", "itl", "decode_step", "prefill"):
            h = m.get(f"engine.{key}_ms")
            if isinstance(h, obs_metrics.Histogram) and h.count:
                lat[f"{key}_p50_ms"] = h.percentile(0.50)
                lat[f"{key}_p95_ms"] = h.percentile(0.95)
        solve = m.get("ilp.solve_ms")
        if isinstance(solve, obs_metrics.Histogram) and solve.count:
            lat["ilp_solve_p50_ms"] = solve.percentile(0.50)
            # percentile() clamps to the observed extremes, so 1.0 is the
            # exact max — the number the < 50 ms paper-claim gate reads
            lat["ilp_solve_max_ms"] = solve.percentile(1.0)
        return EngineStats(
            iterations=c("iterations"),
            decode_steps=c("decode_steps"),
            slot_steps=c("slot_steps"),
            padded_slot_steps=c("padded_slot_steps"),
            prefill_calls=c("prefill_calls"),
            prefill_tokens=c("prefill_tokens"),
            prefill_compiles=c("prefill_compiles"),
            act_quant_reused=c("act_quant_reused"),
            decode_attn_route=self.decode_attn_route,
            admitted=c("admitted"),
            completed=c("completed"),
            tokens_generated=c("tokens_generated"),
            prefill_flops_saved=m.value("engine.prefill_flops_saved"),
            prefix_hit_tokens=c("prefix_hit_tokens"),
            kv_unique_pages=c("kv_unique_pages"),
            admissions_deferred_pool=int(
                m.value("scheduler.admissions_deferred_pool")
            ),
            alerts_fired=int(m.value(obs_monitor.ALERTS_FIRED)),
            spec_rounds=int(m.value("spec.rounds")),
            spec_draft_tokens=int(m.value("spec.draft_tokens")),
            spec_accepted_tokens=int(m.value("spec.accepted_tokens")),
            policy_swaps=c("policy_swaps"),
            policy_swaps_down=c("policy_swaps_down"),
            ilp_solves=c("ilp_solves"),
            admissions_deferred_swap=int(
                m.value("scheduler.admissions_deferred_swap")
            ),
            active_policy=self._active_policy,
            t_prefill_s=m.value("engine.t_prefill_s"),
            t_decode_s=m.value("engine.t_decode_s"),
            latency=lat,
        )

    def _fresh_state(self):
        """Allocate the per-slot decode state and, under a mesh, place it
        on its resolved shardings (computed once, then reused by reset).
        The paged layout also rebuilds its host-side page pool here: pool
        and device state are one consistent unit (empty table, all free)."""
        self._slot_pages: List[Optional[List[int]]] = [None] * self.ecfg.slots
        kw = {}
        if self._paged:
            self.pool = qkv.PagePool(
                self.layout.pool_pages(self.ecfg.slots, self.ecfg.cache_len),
                self.ecfg.page_size,
            )
            kw["layout"] = self.layout
        state = self.adapter.init_state(
            self.ecfg.slots,
            self.ecfg.cache_len,
            dtype=self.ecfg.state_dtype,
            per_slot=True,
            **kw,
        )
        if self._mesh is not None:
            if self._state_shardings is None:
                from repro.dist import sharding as shd

                specs = shd.decode_state_specs(self.cfg, state, self.axes)
                self._state_shardings = shd.named(self._mesh, specs)
            state = jax.device_put(state, self._state_shardings)
        return state

    def reset(self, policy: Optional[str] = None) -> None:
        """Clear queue, slots, metrics/trace epoch, and decode state — but
        keep the jitted prefill/decode/insert/evict functions, so an engine
        can serve many request sets without recompiling. Previously
        captured ``stats`` snapshots (and the old registry/trace objects)
        stay frozen; the engine starts a fresh observability epoch."""
        self._init_obs()
        self.scheduler = Scheduler(
            policy or self.scheduler.policy,
            self.prefill_chunk,
            metrics=self.metrics,
        )
        self.slots = [None] * self.ecfg.slots
        self.completions = {}
        self._submitted = {}
        self._act_reuse_base = getattr(self.adapter, "act_quant_reused", 0)
        self.state = self._fresh_state()
        self._set_cache_gauges()

    # -- queue --------------------------------------------------------------
    def submit(self, req: Request) -> None:
        """Validate and enqueue a request."""
        if req.prompt_len < 1 or req.max_new < 1:
            raise ValueError(f"request {req.rid}: empty prompt or max_new < 1")
        in_flight = {s.req.rid for s in self.slots if s is not None}
        taken = in_flight | set(self.completions)
        taken.update(r.rid for r in self.scheduler.pending)
        if req.rid in taken:
            raise ValueError(
                f"request id {req.rid} already queued, running, or completed"
            )
        windowed = bool(self.cfg.sliding_window or self.cfg.local_window)
        if not windowed and req.prompt_len + req.max_new > self.ecfg.cache_len:
            raise ValueError(
                f"request {req.rid}: prompt {req.prompt_len} + max_new "
                f"{req.max_new} exceeds cache_len {self.ecfg.cache_len} "
                "(full-attention arch cannot ring-wrap without changing "
                "results)"
            )
        self.scheduler.submit(req)
        self._submitted[req.rid] = time.perf_counter()

    def submit_all(self, reqs) -> None:
        for r in reqs:
            self.submit(r)

    # -- internals ----------------------------------------------------------
    def _clear_freed(self, freed: List[int]) -> None:
        """Clear device ``pos`` rows of pages whose refcount hit zero.
        Load-bearing: a recycled page keeping a previous occupant's ``pos``
        rows would be wrongly attendable the moment it is remapped. Ids are
        padded to a fixed (n_pages,) shape so this compiles once."""
        if not freed:
            return
        ids = np.full((self.pool.n_pages,), -1, np.int32)
        ids[: len(freed)] = freed
        self.state = self._free_pages(self.state, jnp.asarray(ids))

    def _matmul_route(self) -> str:
        """The packed-matmul impl serving this engine's traces, for
        latency attribution (dispatch counts routes at trace time; the
        executed graph runs the dominant one)."""
        from repro.runtime import dispatch as _dispatch

        return _dispatch.dominant_route(self.metrics)

    def _occupied(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s is not None]

    def _free(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s is None]

    def _finish(self, idx: int, now: int) -> None:
        slot = self.slots[idx]
        assert slot is not None
        rid = slot.req.rid
        logits = slot.logits
        if logits is not None:
            logits = np.stack(logits[: slot.req.max_new])
        self.completions[rid] = Completion(
            rid=rid,
            prompt_len=slot.req.prompt_len,
            tokens=slot.gen[: slot.req.max_new],
            admitted_at=slot.admitted_at,
            finished_at=now,
            spec_drafted=slot.spec_drafted,
            spec_accepted=slot.spec_accepted,
            policy_id=slot.policy_id,
            logits=logits,
        )
        m = self.metrics
        m.counter("engine.completed").inc()
        m.counter("engine.tokens_generated").inc(len(slot.gen[: slot.req.max_new]))
        self.slots[idx] = None
        m.gauge("engine.slot_occupancy").set(len(self._occupied()))
        self.state = self._evict(self.state, jnp.asarray(idx, jnp.int32))
        if self._paged:
            pages = self._slot_pages[idx]
            self._slot_pages[idx] = None
            if pages:
                # drop this slot's references; registry pins keep shared
                # prefix pages alive for future remaps
                self._clear_freed(self.pool.release(pages))
            m.gauge("engine.kv_unique_pages").set(
                self.pool.unique_pages_in_use
            )
            self._set_pool_gauges()
        if self.trace is not None:
            ts = self.trace.now()
            track = obs_trace.req_track(rid)
            self.trace.instant(
                "complete",
                track=track,
                ts=ts,
                rid=rid,
                tokens=len(slot.gen),
                iteration=now,
            )
            self.trace.span(
                "request",
                slot.ts_admit,
                ts,
                track=track,
                rid=rid,
                prompt_len=slot.req.prompt_len,
                tokens=len(slot.gen),
                slot=idx,
            )
            self.trace.instant("evict", track=track, rid=rid, slot=idx)

    def _mark_done(self, idx: int, now: int) -> None:
        """Sequence finished: free immediately (continuous) or hold the slot
        until the whole round drains (fixed-batch padding semantics)."""
        slot = self.slots[idx]
        assert slot is not None
        slot.done = True
        if not self.scheduler.hold_round:
            self._finish(idx, now)

    def _admit(self, req: Request, idx: int, now: int) -> None:
        """Prefill ``req`` into slot ``idx``: its first token is in hand
        when this returns."""
        with obs_trace.span("engine.admit", self.trace):
            if self._paged:
                self._admit_paged(req, idx, now)
            else:
                self._admit_ring(req, idx, now)

    def _admit_paged(self, req: Request, idx: int, now: int) -> None:
        """Paged admission: longest registered page-aligned prefix becomes
        a page-table remap (no recompute, attended via COW-refcounted
        shared pages); only the unshared suffix runs through chunked-append
        prefill (fixed chunk shape — one compile, no prompt bucketing)."""
        toks = np.asarray(req.tokens, np.int32)
        plen = req.prompt_len
        ps = self.ecfg.page_size
        pool = self.pool
        chain = prefix_chain_keys(toks, ps)
        # cap the hit one page short of covering the whole prompt: at least
        # one suffix token must run to produce the first token's logits
        shared = list(pool.lookup_prefix(chain[: (plen - 1) // ps]))
        hit_tokens = len(shared) * ps
        # this slot's reference on the donor's pages, taken BEFORE the
        # allocation: it may evict LRU prefixes, the one just hit included
        pool.ref(shared)
        fresh, freed = pool.alloc_with_freed(self._pages_per_slot - len(shared))
        self._clear_freed(freed)
        table_row = shared + fresh
        ts_admit = (
            self.trace.now() if self.trace is not None else time.perf_counter()
        )
        chunk_len = max(ps, self.prefill_chunk // ps * ps)
        with obs_trace.span("engine.launch", self.trace):
            t0 = time.perf_counter()
            self.state = self._map_slot(
                self.state,
                jnp.asarray(idx, jnp.int32),
                jnp.asarray(np.asarray(table_row, np.int32)),
            )
            first_arr = None
            for start in range(hit_tokens, plen, chunk_len):
                n = min(chunk_len, plen - start)
                chunk = np.zeros((1, chunk_len), np.int32)
                chunk[0, :n] = toks[start : start + n]
                qpos = np.full((chunk_len,), -1, np.int32)
                qpos[:n] = np.arange(start, start + n, dtype=np.int32)
                logits, self.state = self._append(
                    self.params,
                    jnp.asarray(chunk),
                    jnp.asarray(qpos),
                    jnp.asarray(idx, jnp.int32),
                    jnp.asarray(n - 1, jnp.int32),
                    self.state,
                )
                first_arr = jnp.argmax(logits[0], -1)
            jax.block_until_ready((first_arr, self.state))
            dt = time.perf_counter() - t0
        self._prefill_shapes.add(chunk_len)
        # register this prompt's own complete-page chains: the next prompt
        # sharing them prefills only its suffix
        k_full = plen // ps
        pool.register_prefix(chain[:k_full], table_row[:k_full])
        self._slot_pages[idx] = table_row
        self._admitted(
            req, idx, now, first_arr, logits, ts_admit, dt,
            plen - hit_tokens, hit_pages=len(shared),
        )

    def _admit_ring(self, req: Request, idx: int, now: int) -> None:
        toks = np.asarray(req.tokens, np.int32)
        plen = req.prompt_len
        if self._bucket:
            blen = min(
                bucket_length(plen, self.ecfg.bucket_min), self.ecfg.cache_len
            )
            if blen > plen:
                toks = np.pad(toks, (0, blen - plen))
        inputs = {"tokens": jnp.asarray(toks)[None, :]}
        if req.extra_inputs:
            inputs.update(
                {k: jnp.asarray(v)[None] for k, v in req.extra_inputs.items()}
            )
        ts_admit = self.trace.now() if self.trace is not None else time.perf_counter()
        with obs_trace.span("engine.launch", self.trace):
            t0 = time.perf_counter()
            if self._bucket:
                logits, row = self._prefill(
                    self.params, inputs, jnp.asarray(plen, jnp.int32)
                )
            else:
                logits, row = self._prefill(self.params, inputs)
            row = self.adapter.state_per_slot(row)
            self.state = self._insert(
                self.state, row, jnp.asarray(idx, jnp.int32)
            )
            first_arr = jnp.argmax(logits[0], -1)
            # fence the FULL output tree (sampled token AND the inserted
            # cache state), so the stamp covers device work, not dispatch
            # latency
            jax.block_until_ready((first_arr, self.state))
            dt = time.perf_counter() - t0
        self._prefill_shapes.add(int(toks.shape[-1]))
        # the prefill span counts the bucketed (padded) tokens it ran
        self._admitted(
            req, idx, now, first_arr, logits, ts_admit, dt, plen,
            span_tokens=int(toks.shape[-1]),
        )

    def _admitted(self, req, idx, now, first_arr, logits, ts_admit, dt,
                  prefill_tokens, hit_pages=0, span_tokens=None) -> None:
        """The host side of an admission once its prefill launch is fenced:
        the first token's copy-back, the slot, counters and trace events.
        TTFT runs from ``submit`` to the first token in hand, so the queue
        wait counts; ``engine.prefill_ms`` is the fenced launch alone.
        ``hit_pages`` shared prefix pages were remapped, not prefilled."""
        hit_tokens = hit_pages * self.ecfg.page_size
        with obs_trace.span("engine.sample", self.trace):
            first = int(first_arr)
            row = (
                np.asarray(logits[0], np.float32)
                if self.ecfg.record_logits
                else None
            )
        t_first = time.perf_counter()
        m = self.metrics
        m.counter("engine.t_prefill_s").inc(dt)
        m.counter("engine.prefill_calls").inc()
        m.counter("engine.prefill_tokens").inc(prefill_tokens)
        m.counter("engine.admitted").inc()
        if hit_tokens:
            m.counter("engine.prefix_hit_tokens").inc(hit_tokens)
            m.counter("engine.prefill_flops_saved").inc(
                hit_tokens * self._flops_per_token
            )
        m.gauge("engine.prefill_compiles").set(len(self._prefill_shapes))
        if self._paged:
            m.gauge("engine.kv_unique_pages").set(self.pool.unique_pages_in_use)
            self._set_pool_gauges()
        m.gauge("engine.act_quant_reused").set(
            getattr(self.adapter, "act_quant_reused", 0) - self._act_reuse_base
        )
        m.histogram("engine.prefill_ms").observe(dt * 1e3)
        t_submit = self._submitted.pop(req.rid, t_first - dt)
        m.histogram("engine.ttft_ms").observe((t_first - t_submit) * 1e3)
        obs_health.attribute_latency(m, "matmul", self._matmul_route(), dt)
        self.slots[idx] = _Slot(
            req, first, now, ts_admit, ts_admit + dt, self._active_policy
        )
        if row is not None:
            self.slots[idx].logits = [row]
        m.gauge("engine.slot_occupancy").set(len(self._occupied()))
        if self.trace is not None:
            stamp = (
                {"policy": self._active_policy} if self._active_policy else {}
            )
            track = obs_trace.req_track(req.rid)
            self.trace.instant(
                "admit",
                track=track,
                ts=ts_admit,
                rid=req.rid,
                slot=idx,
                prompt_len=req.prompt_len,
                iteration=now,
                **({"prefix_hit_tokens": hit_tokens} if self._paged else {}),
            )
            if hit_tokens:
                # a remap is NOT a prefill: the explicit event carries what
                # the page-table hit skipped so reconcile can tell a shared
                # prefix from a suspiciously fast prefill span
                self.trace.instant(
                    "prefix_hit",
                    track=track,
                    ts=ts_admit,
                    rid=req.rid,
                    pages_reused=hit_pages,
                    tokens=hit_tokens,
                    flops_saved=hit_tokens * self._flops_per_token,
                )
            self.trace.span(
                "prefill",
                ts_admit,
                ts_admit + dt,
                track=track,
                rid=req.rid,
                tokens=prefill_tokens if span_tokens is None else span_tokens,
            )
            self.trace.instant(
                "first_token",
                track=track,
                ts=ts_admit + dt,
                rid=req.rid,
                token=first,
                **stamp,
            )
        if req.max_new == 1 or first == self.ecfg.eos_id:
            self._mark_done(idx, now)

    def _decode_step(self, now: int) -> None:
        span, rec = obs_trace.span, self.trace
        with span("engine.decode", rec):
            n = self.ecfg.slots
            toks = np.zeros((n, 1), np.int32)
            pos = np.full((n,), -1, np.int32)
            live: List[int] = []
            for i, s in enumerate(self.slots):
                if s is not None and not s.done:
                    toks[i, 0] = s.next_tok
                    pos[i] = s.next_pos
                    live.append(i)
            with span("engine.launch", rec):
                t0 = time.perf_counter()
                logits, self.state = self._decode(
                    self.params, jnp.asarray(toks), jnp.asarray(pos), self.state
                )
                nxt_arr = jnp.argmax(logits, -1)
                # fence the FULL output tree (next tokens AND the appended
                # cache state), so the stamp covers device work, not
                # dispatch latency
                jax.block_until_ready((nxt_arr, self.state))
                dt = time.perf_counter() - t0
            with span("engine.sample", rec):
                nxt = np.asarray(nxt_arr)
                rows = (
                    np.asarray(logits, np.float32)
                    if self.ecfg.record_logits
                    else None
                )
        self._kv_drift_sample()
        with span("engine.bookkeeping", rec):
            m = self.metrics
            m.counter("engine.t_decode_s").inc(dt)
            m.counter("engine.decode_steps").inc()
            m.counter("engine.slot_steps").inc(len(live))
            m.counter("engine.padded_slot_steps").inc(len(self._occupied()))
            m.gauge("engine.act_quant_reused").set(
                getattr(self.adapter, "act_quant_reused", 0)
                - self._act_reuse_base
            )
            m.histogram("engine.decode_step_ms").observe(dt * 1e3)
            obs_health.attribute_latency(
                m, "decode_attn", self.decode_attn_route, dt
            )
            ts1 = rec.now() if rec is not None else time.perf_counter()
            if rec is not None:
                rec.span(
                    "decode_step", ts1 - dt, ts1, slots=len(live), iteration=now
                )
            itl = m.histogram("engine.itl_ms")
            for i in live:
                s = self.slots[i]
                if rows is not None:
                    s.logits.append(rows[i])
                s.gen.append(int(nxt[i]))
                s.next_tok = int(nxt[i])
                s.next_pos += 1
                itl.observe((ts1 - s.ts_last_token) * 1e3)
                s.ts_last_token = ts1
                if rec is not None:
                    rec.instant(
                        "token",
                        track=obs_trace.req_track(s.req.rid),
                        ts=ts1,
                        rid=s.req.rid,
                        token=int(nxt[i]),
                        iteration=now,
                        **({"policy": s.policy_id} if s.policy_id else {}),
                    )
                if len(s.gen) >= s.req.max_new or nxt[i] == self.ecfg.eos_id:
                    self._mark_done(i, now)

    def _kv_drift_sample(self) -> None:
        """KV-scale drift, every ``health_every``-th decode launch: sampled
        host-side from the already-fenced state (materialized write-time
        scales), so the jitted graph never sees it. Runs before the
        launch is counted, so the stride counts this launch."""
        he = self.ecfg.health_every
        steps = int(self.metrics.value("engine.decode_steps")) + 1
        if he and steps % he == 0:
            with obs_trace.span("engine.kv_drift", self.trace):
                self._kv_drift.publish(
                    self.metrics, self._kv_drift.update(self.state)
                )

    # -- self-speculative decode --------------------------------------------
    def _spec_draft_body(self, steps: int, p, tok, pos, state):
        """``steps`` single-token draft-policy decodes inside one
        ``lax.scan`` (argmax stays in-graph), writing draft KV rows at
        p..p+steps-1. Returns (drafts (n, steps), state)."""

        def body(carry, _):
            tok, pos, st = carry
            logits, st = self.adapter.decode(p, tok, pos, st)
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            return (nxt[:, None], jnp.where(pos < 0, pos, pos + 1), st), nxt

        (_, _, st), drafts = jax.lax.scan(
            body, (tok, pos, state), None, length=steps
        )
        return drafts.T, st

    def _spec_verify_fn(self, p, tok, drafts, pos, remaining, state):
        """Multi-token TARGET pass over [cur, d1..dk] at positions p..p+k
        (overwriting every draft KV row with target-computed rows and
        writing row p+k), then — still in-graph — the greedy acceptance
        walk, emission truncation (max_new remaining first, then first
        EOS: the exact order a token-at-a-time engine stops in), and the
        KV rollback past each slot's last fed row. Free slots (pos -1)
        ride along at sentinel positions and an untouchable rollback cut.
        Returns (targets (n, k+1), accept_len (n,), emit_count (n,),
        state)."""
        k = drafts.shape[1]
        vtok = jnp.concatenate([tok, drafts], axis=1)
        off = jnp.arange(k + 1, dtype=jnp.int32)
        vpos = jnp.where(pos[:, None] < 0, -1, pos[:, None] + off[None])
        logits, st = self.adapter.verify(p, vtok, vpos, state)
        targets = jnp.argmax(logits, -1).astype(jnp.int32)  # (n, k+1)
        accept = jnp.cumprod(
            (drafts == targets[:, :k]).astype(jnp.int32), axis=1
        )
        a = accept.sum(axis=1)  # accepted draft prefix length
        emit = jnp.minimum(a + 1, remaining)
        eos_id = self.ecfg.eos_id
        if eos_id is not None:
            hits = (targets == eos_id) & (off[None] < emit[:, None])
            first = jnp.argmax(hits, axis=1).astype(jnp.int32)
            emit = jnp.where(hits.any(axis=1), first + 1, emit)
        cut = jnp.where(pos < 0, jnp.int32(2**30), pos + emit)
        st = lm.rollback_decode_state(st, cut)
        return targets, a, emit, st

    def _spec_draft(self, steps: int):
        """Jitted draft pass for one round length — one dispatch instead
        of k. Distinct ``steps`` values compile separately; the host clamp
        in ``_spec_round`` keeps that set tiny (k plus end-of-sequence
        remainders)."""
        fn = self._spec_draft_jits.get(steps)
        if fn is None:
            fn = jax.jit(
                lambda p, tok, pos, state: self._spec_draft_body(
                    steps, p, tok, pos, state
                ),
                donate_argnums=(3,),
            )
            self._spec_draft_jits[steps] = fn
        return fn

    def _spec_fused(self, steps: int):
        """The traceless fast path: draft scan + verify + acceptance +
        rollback as ONE jitted launch — a whole speculative round costs a
        single dispatch and a single fence. Used when ``trace`` is off
        (the bench's measured configuration); with tracing on, the round
        splits into draft/verify launches so the phase spans are honest
        fenced timings rather than estimates."""
        fn = self._spec_fused_jits.get(steps)
        if fn is None:

            def round_fn(tp, dp, tok, pos, remaining, state):
                # the scopes keep draft and verify apart in a device trace
                with jax.named_scope("spec_draft"):
                    drafts, state = self._spec_draft_body(
                        steps, dp, tok, pos, state
                    )
                with jax.named_scope("spec_verify"):
                    return self._spec_verify_fn(
                        tp, tok, drafts, pos, remaining, state
                    )

            fn = jax.jit(round_fn, donate_argnums=(5,))
            self._spec_fused_jits[steps] = fn
        return fn

    def _spec_round(self, now: int) -> None:
        """One speculative round over all live slots: the low-bit DRAFT
        policy proposes k tokens (one scan launch, writing draft KV rows
        at p..p+k-1), the searched TARGET policy verifies [cur, d1..dk]
        in one multi-token pass (overwriting every draft row with
        target-computed KV and writing row p+k), greedy acceptance walks
        the longest matching prefix, and rows past each slot's last fed
        token are rolled back. Emits 1..k+1 tokens per slot, all of them
        the target policy's own greedy chain — token- and KV-bitwise
        identical to ``_decode_step`` by construction; speculation only
        changes how many launches that chain costs."""
        live = [
            i for i, s in enumerate(self.slots) if s is not None and not s.done
        ]
        k = min(
            self._spec_k,
            min(self.slots[i].req.max_new - len(self.slots[i].gen) for i in live),
        )
        if k < 1:
            return self._decode_step(now)
        span, rec = obs_trace.span, self.trace
        with span("engine.decode", rec):
            n = self.ecfg.slots
            toks = np.zeros((n, 1), np.int32)
            pos = np.full((n,), -1, np.int32)
            remaining = np.zeros((n,), np.int32)
            for i in live:
                s = self.slots[i]
                toks[i, 0] = s.next_tok
                pos[i] = s.next_pos
                remaining[i] = s.req.max_new - len(s.gen)
            with span("engine.launch", rec):
                t0 = time.perf_counter()
                if rec is not None:
                    # two launches, fenced between, so the draft/verify
                    # phase spans carry measured durations; acceptance,
                    # truncation and rollback still run inside the verify
                    # launch
                    drafts, self.state = self._spec_draft(k)(
                        self.draft_params,
                        jnp.asarray(toks),
                        jnp.asarray(pos),
                        self.state,
                    )
                    jax.block_until_ready((drafts, self.state))
                    t_draft = time.perf_counter() - t0
                    targets, acc_arr, emit_arr, self.state = self._spec_verify(
                        self.params,
                        jnp.asarray(toks),
                        drafts,
                        jnp.asarray(pos),
                        jnp.asarray(remaining),
                        self.state,
                    )
                else:
                    # traceless fast path: the whole round is ONE dispatch
                    t_draft = 0.0
                    targets, acc_arr, emit_arr, self.state = self._spec_fused(
                        k
                    )(
                        self.params,
                        self.draft_params,
                        jnp.asarray(toks),
                        jnp.asarray(pos),
                        jnp.asarray(remaining),
                        self.state,
                    )
                jax.block_until_ready((targets, acc_arr, emit_arr, self.state))
                dt = time.perf_counter() - t0
            with span("engine.sample", rec):
                t_np = np.asarray(targets)
                a_np = np.asarray(acc_arr)
                e_np = np.asarray(emit_arr)
        self._kv_drift_sample()
        with span("engine.bookkeeping", rec):
            m = self.metrics
            emits: Dict[int, List[int]] = {}
            accepted_total = 0
            for i in live:
                s = self.slots[i]
                accepted_total += int(a_np[i])
                s.spec_drafted += k
                s.spec_accepted += int(a_np[i])
                m.histogram("spec.accept_len").observe(float(a_np[i]))
                emit = [int(x) for x in t_np[i, : e_np[i]]]
                emits[i] = emit
                s.gen.extend(emit)
                s.next_tok = emit[-1]
                s.next_pos += len(emit)
            m.counter("engine.t_decode_s").inc(dt)
            m.counter("engine.decode_steps").inc()
            m.counter("engine.slot_steps").inc(len(live))
            m.counter("engine.padded_slot_steps").inc(len(self._occupied()))
            m.counter("spec.rounds").inc()
            m.counter("spec.draft_tokens").inc(k * len(live))
            m.counter("spec.accepted_tokens").inc(accepted_total)
            m.gauge("engine.act_quant_reused").set(
                getattr(self.adapter, "act_quant_reused", 0) - self._act_reuse_base
            )
            m.histogram("engine.decode_step_ms").observe(dt * 1e3)
            obs_health.attribute_latency(m, "decode_attn", self.decode_attn_route, dt)
            ts1 = rec.now() if rec is not None else time.perf_counter()
            if rec is not None:
                rec.span(
                    "decode_step", ts1 - dt, ts1, slots=len(live), iteration=now
                )
                rec.span(
                    "spec_draft",
                    ts1 - dt,
                    ts1 - dt + t_draft,
                    slots=len(live),
                    k=k,
                    iteration=now,
                )
                rec.span(
                    "spec_verify_phase",
                    ts1 - dt + t_draft,
                    ts1,
                    slots=len(live),
                    iteration=now,
                )
                rec.instant(
                    "spec_verify",
                    ts=ts1,
                    drafted=k * len(live),
                    accepted=accepted_total,
                    emitted=sum(len(e) for e in emits.values()),
                    iteration=now,
                )
            itl = m.histogram("engine.itl_ms")
            for i in live:
                s = self.slots[i]
                itl.observe((ts1 - s.ts_last_token) * 1e3)
                s.ts_last_token = ts1
                if rec is not None:
                    for tkn in emits[i]:
                        rec.instant(
                            "token",
                            track=obs_trace.req_track(s.req.rid),
                            ts=ts1,
                            rid=s.req.rid,
                            token=tkn,
                            iteration=now,
                            **({"policy": s.policy_id} if s.policy_id else {}),
                        )
                if (
                    len(s.gen) >= s.req.max_new
                    or s.next_tok == self.ecfg.eos_id
                ):
                    self._mark_done(i, now)

    # -- main loop ----------------------------------------------------------
    def step(self, now: int) -> bool:
        """One engine iteration: release a drained round (fixed policy),
        admit per policy, then decode. Returns False when there is nothing
        left to do."""
        with obs_trace.span("engine.step", self.trace):
            return self._step(now)

    def _step(self, now: int) -> bool:
        span, rec = obs_trace.span, self.trace
        if self.scheduler.hold_round:
            occ = self._occupied()
            if occ and all(self.slots[i].done for i in occ):
                with span("engine.bookkeeping", rec):
                    for i in occ:
                        self._finish(i, now)
        if self.scheduler.has_pending():
            with span("engine.schedule", rec):
                if self.elastic is not None:
                    self._elastic_admission(now)
                # paged KV: hand the scheduler the pool's worst-case
                # obtainable pages so it defers (FIFO) rather than letting
                # an admission race the pool into exhaustion mid-prefill
                picks = self.scheduler.admit(
                    now,
                    self._free(),
                    len(self._occupied()),
                    page_budget=(
                        self.pool.available_count if self._paged else None
                    ),
                    page_need=self._pages_per_slot if self._paged else 0,
                    hold=self._swap_decision is not None,
                )
            for req, idx in picks:
                self._admit(req, idx, now)
        if any(s is not None and not s.done for s in self.slots):
            if self._spec_k:
                self._spec_round(now)
            else:
                self._decode_step(now)
        elif self._occupied():
            pass  # held round finished at admission: released next tick
        elif not self.scheduler.has_pending():
            return False
        with span("engine.monitor", rec):
            self.metrics.counter("engine.iterations").inc()
            self.monitor.check(self.metrics, self.trace)
            if self.on_step is not None:
                self.on_step(self.metrics)
        return True

    def run(self) -> Dict[int, Completion]:
        """Drain the queue; returns {rid: Completion}."""
        now = 0
        while self.step(now):
            now += 1
            if now >= self.ecfg.max_iters:
                raise RuntimeError(
                    f"engine exceeded max_iters={self.ecfg.max_iters} "
                    f"(pending={len(self.scheduler.pending)}, "
                    f"occupied={len(self._occupied())})"
                )
        assert not self._occupied(), "slot leak: occupied slots after drain"
        return self.completions
