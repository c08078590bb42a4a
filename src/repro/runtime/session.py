"""QuantizedSession: compile a searched MPQPolicy into a servable model.

This is the "searched policy -> deployed low-bit model" step. Construction
packs once:

1. validate the policy against the model's QLayer table (stale files fail
   loudly),
2. flatten the scan-stacked param tree into per-site subtrees (one per
   ``lm.iter_sites`` entry — serving decode is one token, so unrolling
   trades nothing and gives every site its *own* searched bit-width with
   statically-shaped packed storage),
3. for every searched projection, select the trained indicator-bank scales
   at the policy's bit-widths and quantize + bit-pack the weight
   (``runtime.packing.pack_linear``) — HBM then holds ``ceil(bits/8)``
   bytes per weight, matching ``MPQPolicy.size_bytes`` to within padding.
   Under a real mesh (``axes`` from ``dist.sharding.make_axes_for``) the
   packing is *shard-aware*: each projection packs per shard along its
   megatron tensor-parallel dim (``dist.sharding.projection_shard_fn``),
   so ``codes`` shard over ``tp`` instead of replicating and per-chip HBM
   is ``packed_bytes(per_shard=True)`` ≈ ``policy.size_bytes(per_shard=
   tp)``. ``param_specs()`` exposes the matching PartitionSpec tree
   (``dist.sharding.packed_specs``) for the engine's in_shardings.

Packing also tags activation-reuse groups: projections on one site whose
(a_bits, signedness, trained bank scale values) coincide get a shared
``PackedLinear.a_group``, letting ``runtime.dispatch.act_reuse_scope``
quantize their common input once per forward (wq/wk/wv; MoE wi/wg) —
counted in ``act_quant_reused`` and surfaced as
``EngineStats.act_quant_reused``.

The session then exposes the engine's model-adapter interface (``prefill``
/ ``decode`` / ``init_state`` / ``state_per_slot``), so
``launch.serve --policy`` runs the packed model through the unmodified
continuous-batching engine. Matmuls route through
``runtime.dispatch.packed_qeinsum`` (Pallas int8/int4 kernels on TPU, the
bit-exact dequant-then-fp fallback elsewhere).

Numerics: with per-tensor bank scales (the default) and ``mode="packed"``,
the dequantized weights and on-the-fly activation fake-quant reproduce the
fake-quant training graph *bitwise* on the fallback route, so greedy
tokens are asserted identical against an ``LMAdapter`` reference engine —
including with int8 KV slots, whose reference is ``kv_quant="fake"``.
``mode="reference"`` keeps fake-quant param dicts (same unrolled forward,
no packing) for A/B debugging of the packing itself.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, Dict, List, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.policy import MPQPolicy
from repro.core.quantizer import (
    bit_range,
    grad_scale,
    lsq_grad_scale_factor,
)
from repro.dist.axes import NO_AXES, MeshAxes
from repro.models import lm
from repro.models import moe as moe_mod
from repro.models.quant_layers import QuantContext
from repro.obs import health as obs_health
from repro.runtime import packing

Array = jax.Array


def _site_key(gidx: int) -> str:
    return f"{gidx:03d}"


def _get_path(tree, path: Tuple[str, ...]):
    for k in path:
        tree = tree[k]
    return tree


def _set_path(tree, path: Tuple[str, ...], leaf):
    for k in path[:-1]:
        tree = tree[k]
    tree[path[-1]] = leaf


def effective_weight_scale(s_bank: Array, idx: int, numel: int, bits: int,
                           w_ndim: Optional[int] = None) -> Array:
    """The scale value the fake-quant training graph actually divides by:
    bank entry (selected on the LAST axis — leading axes are expert
    stacks) -> floor at 1e-9 -> LSQ grad-scale wrapper (identity in exact
    arithmetic, replicated op-for-op for bitwise parity). Per-expert
    selections are returned in the trailing-ones broadcast form
    ``fake_quant_indexed`` uses (e.g. ``(E, 1, 1)`` for a rank-3 weight,
    via ``w_ndim``)."""
    qmax = float(bit_range(bits, True)[1])
    sel = jnp.asarray(s_bank)[..., idx]
    s = jnp.maximum(sel.astype(jnp.float32), 1e-9)
    s = grad_scale(s, lsq_grad_scale_factor(numel, qmax))
    if s.ndim and w_ndim is not None:
        s = s.reshape(s.shape + (1,) * (w_ndim - s.ndim))
    return s


class QuantizedSession:
    """A packed, policy-quantized model behind the engine adapter API."""

    def __init__(self, cfg: ModelConfig, params, policy: MPQPolicy,
                 ctx: Optional[QuantContext] = None,
                 axes: MeshAxes = NO_AXES, *, mode: str = "packed",
                 kv_quant: str = "int8", per_channel: bool = False):
        if mode not in ("packed", "reference"):
            raise ValueError(f"unknown session mode {mode!r}")
        self.cfg = cfg
        self.policy = policy
        self.mode = mode
        self.axes = axes
        ctx = ctx or QuantContext.make(cfg.bits, cfg.quant_act_signed,
                                       compute_dtype=jnp.float32)
        # the reference view of an int8 slot is quantize-dequantize in fp
        kv_ctx = {"packed": kv_quant,
                  "reference": "fake" if kv_quant == "int8" else kv_quant}
        self.ctx = dataclasses.replace(ctx, kv_quant=kv_ctx[mode])
        self._kv_quant = kv_quant
        # per-channel statistics scales lower quantization error but break
        # bitwise parity with the trained per-tensor indicator scales — the
        # token-identity gate requires the default False
        self.per_channel = bool(per_channel)

        self.qlayers = lm.enumerate_qlayers(cfg)
        policy.validate(self.qlayers, bits=cfg.bits)
        self.sites = lm.iter_sites(cfg)
        self._lut = {int(b): i for i, b in enumerate(cfg.bits)}
        self.act_quant_reused = 0      # trace-time hits, see dispatch
        # per-site pack-time health (saturation / scale utilization),
        # computed host-side in _build_params from the materialized weights
        # and the scales packing actually used; the engine publishes it
        # into its registry each epoch (obs.health.publish_pack_health)
        self.pack_health: Dict[str, Dict[str, float]] = {}
        # obs.metrics.MetricsRegistry shared by the engine (it assigns this
        # at build/reset): _forward binds it so dispatch counts the routes
        # each packed matmul resolves to, per trace
        self.metrics = None
        # Off-TPU, the model axis is a STORAGE axis only: packed codes
        # shard over tp in HBM and gather at use (dispatch docstring), but
        # the layer graph keeps no model-sharded intermediates — compute
        # splits over dp alone (``dist.axes.dp_only`` rationale). On a TPU
        # backend the full megatron split stays on, where the
        # int-accumulating kernel routes make the eqn split exact.
        from repro.dist.axes import dp_only
        self.compute_axes = axes
        if axes.enabled and jax.default_backend() != "tpu":
            self.compute_axes = dp_only(axes)
        self.params = self._build_params(params)

    # -- construction -------------------------------------------------------
    def _site_params(self, params, site):
        """(a private copy of the site's param dicts, the slice that takes
        a leaf to this site). A scanned body's leaves keep their stack axis
        until ``take`` slices them: packing slices one projection at a
        time, so a layer's float weights are never copied whole."""
        seg, idx = site.segment.split(".")
        sub = jax.tree.map(lambda a: a, params[seg][idx])
        if seg == "body":
            return sub, lambda a: a[site.unit]
        return sub, lambda a: a

    def _build_params(self, params) -> Dict[str, Any]:
        from repro.dist import sharding

        by_site: Dict[int, List] = {}
        for q in self.qlayers:
            by_site.setdefault((q.segment, q.unit), []).append(q)
        shard_info = (sharding.projection_shard_fn(self.cfg, self.axes)
                      if self.axes.enabled else None)

        out: Dict[str, Any] = {
            k: params[k] for k in params if k not in ("prefix", "body",
                                                      "suffix")
        }
        sites_p: Dict[str, Any] = {}
        self._site_bits: Dict[str, Any] = {}
        self._shard_plan: Dict[str, int] = {}
        for site in self.sites:
            key = _site_key(site.gidx)
            sp, take = self._site_params(params, site)
            bits_d: Dict[str, Any] = {}
            packed_paths: List[Tuple[str, ...]] = []
            for q in by_site[(site.segment, site.unit)]:
                leaf = jax.tree.map(take, _get_path(sp, q.path))
                w_idx = self._lut[self.policy.w_bits[q.name]]
                a_idx = self._lut[self.policy.a_bits[q.name]]
                if self.mode == "packed":
                    wb = int(self.policy.w_bits[q.name])
                    s_w = effective_weight_scale(leaf["s_w"], w_idx,
                                                 leaf["w"].size, wb,
                                                 w_ndim=leaf["w"].ndim)
                    sd, sc = (None, 1)
                    if shard_info is not None:
                        name = "/".join(("sites", key) + q.path + ("w",))
                        sd, sc = shard_info(name, tuple(leaf["w"].shape))
                    self._shard_plan[q.name] = sc
                    pl = packing.pack_linear(
                        leaf["w"], wb, s_w,
                        int(self.policy.a_bits[q.name]),
                        jnp.asarray(leaf["s_a"])[..., a_idx],
                        a_signed=self.cfg.quant_act_signed,
                        per_channel=self.per_channel,
                        shard_dim=sd, shard_count=sc)
                    # health from the scale the packing actually used
                    # (pl.scale covers both bank and per-channel modes)
                    self.pack_health[q.name] = obs_health.site_health(
                        leaf["w"], wb, pl.scale)
                    _set_path(sp, q.path, pl)
                    packed_paths.append(q.path)
                else:
                    d: Dict[str, Any] = {}
                    lm._nest(d, q.path, {"w": w_idx, "a": a_idx})
                    # merged below via bits_d
                    bits_d = _merge(bits_d, d)
                del leaf   # one projection's float slice at a time
            sp = jax.tree.map(
                lambda a: a if packing.is_packed(a) else take(a), sp,
                is_leaf=packing.is_packed)
            _tag_act_groups(sp, packed_paths, key)
            sites_p[key] = sp
            self._site_bits[key] = bits_d if self.mode == "reference" else None
        out["sites"] = sites_p
        return out

    # -- accounting ---------------------------------------------------------
    def packed_bytes(self, per_shard: bool = False) -> int:
        """Measured HBM bytes of the packed weight codes.

        ``per_shard=True`` gives the per-device view under the session's
        mesh: tensor-parallel-sharded leaves count ``bytes / shard_count``,
        replicated ones their full bytes — comparable against
        ``policy.size_bytes(qlayers, per_shard=axes.tp_size)``."""
        if per_shard:
            return packing.tree_per_shard_bytes(self.params)
        return packing.tree_packed_bytes(self.params)

    def param_specs(self):
        """PartitionSpec tree for ``self.params`` under the session's axes
        (``dist.sharding.packed_specs``) — the engine's in_shardings hook."""
        from repro.dist import sharding
        return sharding.packed_specs(self.cfg, self.params, self.axes)

    def per_shard_policy_bytes(self) -> float:
        """Per-chip weight-bytes budget under this session's ACTUAL shard
        plan: each searched projection's policy bytes divided by the
        tensor-parallel factor its partition rule grants it. Equals
        ``policy.size_bytes(per_shard=tp)`` when every projection shards
        (the limpq-demo case); on archs where the divisibility fallbacks
        legitimately replicate some projections (e.g. heads not dividing
        the model axis) those count in full per chip — the per-chip gate
        must not blame packing for a partition-rule fallback."""
        total = 0.0
        for q in self.qlayers:
            bytes_q = q.w_params * self.policy.w_bits[q.name] / 8.0
            total += bytes_q / max(self._shard_plan.get(q.name, 1), 1)
        return total

    def scale_bytes(self) -> int:
        return packing.tree_scale_bytes(self.params)

    def policy_bytes(self) -> float:
        """What the ILP accounted for: ``MPQPolicy.size_bytes``."""
        return self.policy.size_bytes(self.qlayers)

    def fp_bytes(self, bytes_per_param: int = 4) -> int:
        """Unquantized weight bytes of the searched projections."""
        return sum(q.w_params for q in self.qlayers) * bytes_per_param

    @property
    def kv_quant(self) -> str:
        return self._kv_quant

    @property
    def w_bits_total(self) -> float:
        """Exact packed weight-storage bits for the roofline's bytes term."""
        return self.policy_bytes() * 8.0

    # -- engine adapter API -------------------------------------------------
    def _forward(self, params, x, img_x, mode, states, pos, prefill_cap,
                 slot=None):
        from repro.runtime import dispatch

        # what the state carries beside the sites (the expert-load counter)
        # passes through every mode; a decode launch adds to it
        new_states = {k: v for k, v in (states or {}).items() if k != "sites"}
        new_states["sites"] = {}
        with dispatch.axes_scope(self.axes), \
                dispatch.metrics_scope(self.metrics), \
                moe_mod.load_scope() as loads, \
                dispatch.act_reuse_scope() as scope:
            for site in self.sites:
                key = _site_key(site.gidx)
                st = None if states is None else states["sites"].get(key)
                # the scope is the site's policy-key prefix (``L027``), so
                # a device op's name reads ``L027/mlp_wo/...``
                with jax.named_scope(f"L{key}"):
                    x, st, _ = lm.apply_layer(
                        site.kind, x, params["sites"][key],
                        self._site_bits[key], self.cfg, self.ctx,
                        self.compute_axes, mode=mode, state=st, pos=pos,
                        img_x=img_x, prefill_cap=prefill_cap, slot=slot)
                new_states["sites"][key] = st
        if mode == "decode" and "moe" in new_states and loads:
            new_states["moe"] = count_expert_load(new_states["moe"], loads)
        # trace-time count: quantize ops elided from this compiled graph
        self.act_quant_reused += scope["hits"]
        if self.metrics is not None and scope["hits"]:
            self.metrics.counter("dispatch.act_reuse_hits").inc(scope["hits"])
        return x, new_states

    def prefill(self, params, inputs, *, prefill_cap, true_len=None):
        x, img_x = lm.embed_inputs(params, self.cfg, inputs, self.ctx,
                                   self.compute_axes)
        x, states = self._forward(params, x, img_x, "prefill", None, None,
                                  prefill_cap)
        return lm.finish_prefill(x, states, params, self.cfg, self.ctx,
                                 self.compute_axes, true_len)

    def decode(self, params, tok, pos, states):
        x, _ = lm.embed_inputs(params, self.cfg, {"tokens": tok}, self.ctx,
                               self.compute_axes)
        x, new_states = self._forward(params, x, None, "decode", states, pos,
                                      None)
        logits = lm.lm_head(x, params, self.cfg, self.ctx, self.compute_axes)
        return logits[:, 0], new_states

    def verify(self, params, tok, pos, states):
        """Speculative verify: run S = k+1 tokens per slot in ONE
        multi-token step over the cached KV (``lm`` mode="verify"),
        appending all S rows and attending each query only to rows at
        positions <= its own — via the exact per-route single-token
        attention primitive, so hidden states and written KV rows are
        bitwise what S sequential ``decode`` calls would produce.
        ``tok``/``pos`` are (B, S); returns (logits (B, S, V), states)."""
        x, _ = lm.embed_inputs(params, self.cfg, {"tokens": tok}, self.ctx,
                               self.compute_axes)
        x, new_states = self._forward(params, x, None, "verify", states, pos,
                                      None)
        logits = lm.lm_head(x, params, self.cfg, self.ctx, self.compute_axes)
        return logits, new_states

    def append(self, params, tok, pos, slot, last_idx, states):
        """Chunked (paged) prefill: run a (1, C) token chunk through the
        model for ONE slot, writing KV rows at absolute positions ``pos``
        ((C,), -1 marks pad rows that are dropped at the cache write) into
        that slot's pages. Returns (last-valid-row logits (1, V), states)."""
        x, _ = lm.embed_inputs(params, self.cfg, {"tokens": tok}, self.ctx,
                               self.compute_axes)
        x, new_states = self._forward(params, x, None, "append", states, pos,
                                      None, slot=slot)
        x_last = jax.lax.dynamic_slice_in_dim(x, last_idx, 1, axis=1)
        logits = lm.lm_head(x_last, params, self.cfg, self.ctx,
                            self.compute_axes)
        return logits[:, 0], new_states

    def init_state(self, batch, capacity, dtype, per_slot=True, layout=None):
        """Every site's decode state, and for a model with MoE layers the
        expert-load counter (``count_expert_load``) under ``"moe"``."""
        kv = "int8" if self.ctx.kv_quant == "int8" else "none"
        state = {"sites": {
            _site_key(s.gidx): lm.init_site_state(
                self.cfg, s.kind, batch, capacity, dtype=dtype,
                per_slot=per_slot, kv_quant=kv, layout=layout)
            for s in self.sites}}
        n_moe = sum(s.kind == "moe" for s in self.sites)
        if n_moe:
            state["moe"] = {
                "tokens": jnp.zeros((n_moe, self.cfg.moe.n_experts),
                                    jnp.int32),
                "hits": jnp.zeros((n_moe,), jnp.int32),
                "launches": jnp.zeros((), jnp.int32)}
        return state

    def state_per_slot(self, row):
        return lm.decode_state_per_slot(row)

    # -- persistence --------------------------------------------------------
    @classmethod
    def from_checkpoint(cls, directory: str, cfg: ModelConfig, *,
                        step: Optional[int] = None,
                        ctx: Optional[QuantContext] = None,
                        axes: MeshAxes = NO_AXES,
                        **kwargs) -> "QuantizedSession":
        """Restore a ``checkpoint.save_serving_bundle`` artifact (params +
        policy) and pack it for serving.

        The bundled policy is validated against ``cfg``'s QLayer table
        BEFORE the param restore touches the template: a stale or foreign
        bundle fails loudly with the same ``MPQPolicy.validate`` message
        path as ``lm.bits_from_policy``, instead of a cryptic
        missing-array/shape error from the checkpoint reader."""
        from repro import checkpoint as ckpt

        template = lm.init_params(jax.random.PRNGKey(0), cfg)
        params, policy, _ = ckpt.load_serving_bundle(
            directory, template, step=step,
            validate=lambda p: p.validate(lm.enumerate_qlayers(cfg),
                                          bits=cfg.bits))
        return cls(cfg, params, policy, ctx, axes, **kwargs)


def count_expert_load(counter, loads):
    """Add one decode launch to the expert-load counter: ``loads`` holds
    each MoE layer's tokens per expert ((E,) each, in layer order). The
    counter keeps, per MoE layer, the tokens each expert took (``tokens``,
    (n_moe, E)) and the distinct experts it read (``hits``, (n_moe,)), both
    summed over launches, and the launches (``launches``). It stays on the
    device with the rest of the decode state."""
    sizes = jnp.stack(loads)
    return {"tokens": counter["tokens"] + sizes,
            "hits": counter["hits"] + jnp.sum(sizes > 0, axis=-1,
                                              dtype=jnp.int32),
            "launches": counter["launches"] + 1}


def draft_policy(policy: MPQPolicy, qlayers, bits,
                 draft_w_bits: int = 2) -> MPQPolicy:
    """Derive the self-speculative DRAFT policy from the searched target.

    Same layers, same a_bits (so activation quantization — and the
    act-reuse grouping — is bitwise the target's), weights uniformly at
    ``draft_w_bits``. Both policies select from the SAME trained
    indicator banks, so the draft costs zero extra trained state: the
    paper's bit-width menu, read at a second (cheaper) point. The draft
    width must be one of the searched ``bits`` — otherwise there is no
    trained bank entry to select and packing would be meaningless."""
    db = int(draft_w_bits)
    if db not in {int(b) for b in bits}:
        raise ValueError(
            f"draft_w_bits={db} is not in the searched bit set "
            f"{sorted(int(b) for b in bits)}; the draft policy can only "
            "read bit-widths the indicator banks were trained for")
    return MPQPolicy({q.name: db for q in qlayers}, dict(policy.a_bits),
                     meta={"kind": "spec-draft", "draft_w_bits": db,
                           "target": dict(policy.meta)})


class SpecSession(QuantizedSession):
    """Dual-policy pack for self-speculative decoding.

    ONE set of trained weights and banks, TWO packed param trees:
    ``self.params`` is the searched target policy (the quality contract
    — emitted tokens are its greedy tokens, by construction), and
    ``self.draft_params`` is a uniform low-bit (int2/int3) repack of the
    same weights used only to PROPOSE tokens. Both trees run through the
    same ``_forward`` / engine adapter; the engine jits draft steps
    against ``draft_params`` and verify steps against ``params``.

    The draft shares the target's a_bits and indicator-bank scales
    (``draft_policy``), so activation quantization in the draft pass is
    bitwise the target's — the bank-sharing requirement ``ServeConfig``
    validates for ``--speculate``."""

    def __init__(self, cfg: ModelConfig, params, policy: MPQPolicy,
                 ctx: Optional[QuantContext] = None,
                 axes: MeshAxes = NO_AXES, *, draft_w_bits: int = 2,
                 mode: str = "packed", **kwargs):
        if mode != "packed":
            raise ValueError(
                "SpecSession packs two policies over one weight set; "
                "mode='reference' keeps fake-quant params and has nothing "
                "to dual-pack — build a plain QuantizedSession instead")
        super().__init__(cfg, params, policy, ctx, axes, mode=mode, **kwargs)
        self.draft_w_bits = int(draft_w_bits)
        self.policy_draft = draft_policy(policy, self.qlayers, cfg.bits,
                                         self.draft_w_bits)
        # pack the second tree through the same machinery by swapping the
        # active policy; _site_bits/_shard_plan come out identical (packed
        # mode, same shapes) so restoring the policy restores the session
        target_policy, target_health = self.policy, self.pack_health
        self.policy, self.pack_health = self.policy_draft, {}
        self.draft_params = self._build_params(params)
        self.draft_pack_health = self.pack_health
        self.policy, self.pack_health = target_policy, target_health

    def draft_bytes(self) -> int:
        """Measured HBM bytes of the draft tree's packed codes — the bytes
        the roofline charges k times per speculative round."""
        return packing.tree_packed_bytes(self.draft_params)


def bank_fingerprint(params) -> str:
    """Fingerprint of the trained indicator-bank scales.

    Hashes every ``s_w`` / ``s_a`` leaf in sorted-path order. Policy
    variants searched over the same banks carry this stamp in
    ``meta["indicator_family"]``; ``MPQPolicy.validate(family=...)`` then
    rejects a bundle mixing variants from different trainings — their bit
    assignments were learned against scales this checkpoint does not
    have, and a hot-swap between them would break the shared
    activation-quantization contract the token-identity gate relies on.
    """
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    picked = []
    for path, leaf in leaves:
        keys = tuple(str(getattr(p, "key", getattr(p, "name",
                                                   getattr(p, "idx", p))))
                     for p in path)
        if keys and keys[-1] in ("s_w", "s_a"):
            picked.append((keys, leaf))
    if not picked:
        raise ValueError(
            "no indicator-bank scale leaves (s_w/s_a) in params: cannot "
            "fingerprint the bank family — was this checkpoint trained "
            "with learned importance indicators?")
    h = hashlib.sha1()
    for keys, leaf in sorted(picked, key=lambda kv: kv[0]):
        h.update("/".join(keys).encode())
        h.update(np.asarray(leaf, np.float32).tobytes())
    return h.hexdigest()[:16]


class ElasticSession(QuantizedSession):
    """Policy-variant bank for elastic precision serving.

    ONE set of trained weights and indicator banks, N packed param trees
    — one per ``MPQPolicy`` variant (e.g. 3/4/6-bit average budgets
    searched over the same banks; ``launch.elastic.build_variant_bank``).
    Every variant packs ONCE at build through the same policy-swap
    machinery ``SpecSession`` dual-packs with; serving then switches the
    active tree between batches via ``set_active`` — the engine
    ``device_put``s the returned pre-packed tree, so no repacking ever
    happens on the hot path.

    Build fails loudly if any variant's ``meta["indicator_family"]``
    stamp disagrees with ``bank_fingerprint(params)``: variants searched
    from different trainings do not share the activation-quantization
    contract a hot-swap assumes.
    """

    def __init__(self, cfg: ModelConfig, params,
                 variants: Mapping[str, MPQPolicy],
                 ctx: Optional[QuantContext] = None,
                 axes: MeshAxes = NO_AXES, *, active: Optional[str] = None,
                 mode: str = "packed", **kwargs):
        if mode != "packed":
            raise ValueError(
                "ElasticSession packs N policy variants over one weight "
                "set; mode='reference' keeps fake-quant params and has "
                "nothing to swap — build a plain QuantizedSession instead")
        items = [(str(pid), pol) for pid, pol in variants.items()]
        if len(items) < 2:
            raise ValueError(
                "ElasticSession needs >= 2 policy variants; a single "
                "policy is a plain QuantizedSession")
        family = bank_fingerprint(params)
        qlayers = lm.enumerate_qlayers(cfg)
        for pid, pol in items:
            try:
                pol.validate(qlayers, bits=cfg.bits, family=family)
            except ValueError as e:
                raise ValueError(f"policy variant {pid!r}: {e}") from e
        by_id = dict(items)
        active = items[0][0] if active is None else str(active)
        if active not in by_id:
            raise ValueError(
                f"active variant {active!r} not in bank {sorted(by_id)}")
        super().__init__(cfg, params, by_id[active], ctx, axes, mode=mode,
                         **kwargs)
        self.family = family
        self.active_policy = active
        self.variant_policies: Dict[str, MPQPolicy] = by_id
        self.variants: Dict[str, Any] = {active: self.params}
        self.variant_pack_health: Dict[str, Dict[str, Dict[str, float]]] = {
            active: self.pack_health}
        for pid, pol in items:
            if pid == active:
                continue
            # pack through the same machinery by swapping the active
            # policy (the SpecSession dual-pack pattern): _site_bits /
            # _shard_plan come out identical in packed mode, so restoring
            # the policy restores the session
            keep_policy, keep_health = self.policy, self.pack_health
            self.policy, self.pack_health = pol, {}
            self.variants[pid] = self._build_params(params)
            self.variant_pack_health[pid] = self.pack_health
            self.policy, self.pack_health = keep_policy, keep_health

    # -- variant bank -------------------------------------------------------
    def params_for(self, pid: str):
        """The pre-packed param tree of one variant (no packing here)."""
        return self.variants[str(pid)]

    def set_active(self, pid: str):
        """Make ``pid`` the serving variant — accounting (``policy``,
        ``pack_health``, ``packed_bytes``) follows the swap — and return
        its pre-packed tree for the engine to ``device_put``."""
        pid = str(pid)
        if pid not in self.variants:
            raise KeyError(
                f"unknown policy variant {pid!r}: {sorted(self.variants)}")
        self.active_policy = pid
        self.policy = self.variant_policies[pid]
        self.pack_health = self.variant_pack_health[pid]
        self.params = self.variants[pid]
        return self.params

    def variant_bytes(self) -> Dict[str, int]:
        """Measured packed-code HBM bytes per resident variant — what
        keeping the whole bank on-device costs."""
        return {pid: packing.tree_packed_bytes(tree)
                for pid, tree in self.variants.items()}


def _tag_act_groups(sp, packed_paths, site_key: str) -> None:
    """Assign ``PackedLinear.a_group`` reuse tags within one site.

    Two packed projections may share a quantized activation only when
    their quantization of it is bitwise the same op: equal a_bits, equal
    signedness, and equal *values* in the selected trained bank scale.
    The values are concrete here (packing happens eagerly at build), so
    the grouping is exact — a tag is assigned only to groups of two or
    more, and it embeds the site key so identical banks on different
    sites (e.g. the same init value) can never alias across sites."""
    import numpy as np

    groups: Dict[Tuple, List[Tuple[str, ...]]] = {}
    for path in packed_paths:
        pl = _get_path(sp, path)
        fp = (pl.a_bits, pl.a_signed,
              np.asarray(pl.s_a, np.float32).tobytes())
        groups.setdefault(fp, []).append(path)
    gi = 0
    for fp, paths in groups.items():
        if len(paths) < 2:
            continue
        tag = f"{site_key}.a{gi}"
        gi += 1
        for path in paths:
            pl = _get_path(sp, path)
            _set_path(sp, path, dataclasses.replace(pl, a_group=tag))


def _merge(dst: dict, src: dict) -> dict:
    for k, v in src.items():
        if isinstance(v, dict) and isinstance(dst.get(k), dict):
            _merge(dst[k], v)
        else:
            dst[k] = v
    return dst


def summarize(session: QuantizedSession) -> Dict[str, Any]:
    """HBM accounting for logs / the quant-serve benchmark."""
    packed = session.packed_bytes()
    target = session.policy_bytes()
    tp = session.axes.tp_size if session.axes.enabled else 1
    per_shard = session.packed_bytes(per_shard=True)
    shard_target = session.per_shard_policy_bytes()
    return {
        "mode": session.mode,
        "packed_bytes": int(packed),
        "scale_bytes": int(session.scale_bytes()),
        "policy_bytes": float(target),
        "fp32_bytes": int(session.fp_bytes()),
        "packed_vs_policy": packed / target if target else float("nan"),
        "compression_vs_fp32": session.fp_bytes() / packed if packed
        else float("nan"),
        "avg_bits": session.policy.avg_bits(),
        "kv_quant": session.kv_quant,
        "tp_size": int(tp),
        "per_shard_bytes": int(per_shard),
        "per_shard_vs_policy": (per_shard / shard_target if shard_target
                                else float("nan")),
        "act_quant_reused": int(session.act_quant_reused),
    }
