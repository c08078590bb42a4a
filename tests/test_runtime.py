"""repro.runtime — packing round-trips (property-tested), kernel dispatch
exactness vs the fake-quant graph, int8 KV-cache equivalence, policy schema
gating, bit-aware roofline ordering, and the packed serving session
end-to-end through the continuous-batching engine."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st
from jax.sharding import PartitionSpec as P

from repro.configs import smoke_config
from repro.core.policy import MPQPolicy
from repro.core.quantizer import bit_range, fake_quant
from repro.dist import roofline
from repro.dist.axes import NO_AXES
from repro.launch.engine import DecodeEngine, EngineConfig
from repro.launch.scheduler import Request, bucket_length
from repro.models import attention as attn
from repro.models import lm
from repro.models.quant_layers import QuantContext, qdense_init, qeinsum
from repro.runtime import dispatch, kv_cache as qkv, packing
from repro.runtime.session import QuantizedSession, summarize


# ===========================================================================
# packing
# ===========================================================================
@settings(max_examples=12, deadline=None)
@given(st.sampled_from([3, 5, 6, 7]),          # widths the planes layout takes
       st.integers(1, 19),                     # rows (odd counts included)
       st.integers(1, 11),                     # channels (odd counts)
       st.sampled_from([(), (3,)]),            # plain or expert-stacked
       st.integers(0, 2 ** 31 - 1))
def test_pack_unpack_roundtrip(bits, rows, cols, lead, seed):
    """Property: unpack(pack(q, bits)) == q on the signed grid, any shape;
    the planes are one byte array of ``bits * ceil(rows/8)`` rows that
    keeps the leading axes, exactly ``n * bits / 8`` bytes when the rows
    fill whole 8-row groups."""
    r = np.random.default_rng(seed)
    qmin, qmax = bit_range(bits, True)
    q = r.integers(qmin, qmax + 1, size=lead + (rows, cols)).astype(np.int8)
    codes = packing.pack_planes(q, bits)
    assert codes.dtype == jnp.uint8
    assert codes.shape == lead + (bits * -(-rows // 8), cols)
    if rows % 8 == 0:
        assert codes.size * 8 == q.size * bits
    back = packing.unpack_planes(codes, bits, rows)
    assert back.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(back), q)


@settings(max_examples=8, deadline=None)
@given(st.sampled_from([2, 4]), st.integers(1, 17), st.integers(1, 9),
       st.integers(0, 2 ** 31 - 1))
def test_kernel_layout_roundtrip(bits, rows, cols, seed):
    """nib4 / quad2 layouts round-trip with odd contraction dims (padding
    rows are sliced back off)."""
    r = np.random.default_rng(seed)
    qmin, qmax = bit_range(bits, True)
    q = r.integers(qmin, qmax + 1, size=(rows, cols)).astype(np.int8)
    if bits == 4:
        back = packing.unpack_nib4(packing.pack_nib4(q), rows)
    else:
        back = packing.unpack_quad2(packing.pack_quad2(q), rows)
    np.testing.assert_array_equal(np.asarray(back), q)


@pytest.mark.parametrize("bits", [2, 3, 4, 5, 6, 8])
def test_pack_linear_matches_fake_quant(bits):
    """Dequantized packed weights == the fake-quant graph's values, bitwise
    (per-tensor trained scale), and storage is ceil(n*bits/8) + padding."""
    r = np.random.default_rng(bits)
    w = r.normal(size=(13, 9)).astype(np.float32)   # odd dims on purpose
    s = np.float32(0.05)
    pl = packing.pack_linear(w, bits, s, 6, 0.02)
    ref = fake_quant(jnp.asarray(w), jnp.asarray(s), *bit_range(bits, True))
    np.testing.assert_array_equal(np.asarray(pl.dequant()), np.asarray(ref))
    ideal = (w.size * bits + 7) // 8
    assert pl.packed_bytes >= ideal
    # padding overhead is at most one 8-row group of bit planes (bits * N
    # bytes), and at most one row of the nib4/quad2 layouts
    pad = bits * w.shape[-1] if pl.layout == "planes" else w.shape[-1]
    assert pl.packed_bytes <= ideal + pad


@pytest.mark.parametrize("bits", [2, 3, 4, 5, 8])
@pytest.mark.parametrize("dim,count", [(0, 4), (1, 2)])
def test_shard_aware_packing_bit_identical_per_shard(bits, dim, count):
    """pack_linear(shard_dim=, shard_count=): every shard's slab of the
    packed codes equals packing that weight shard independently, and the
    whole thing round-trips/dequantizes exactly like the plain packing."""
    r = np.random.default_rng(bits)
    w = r.normal(size=(24, 8)).astype(np.float32)
    s = np.float32(0.05)
    plain = packing.pack_linear(w, bits, s, 6, 0.02)
    sh = packing.pack_linear(w, bits, s, 6, 0.02, shard_dim=dim,
                             shard_count=count)
    np.testing.assert_array_equal(np.asarray(sh.unpack()),
                                  np.asarray(plain.unpack()))
    np.testing.assert_array_equal(np.asarray(sh.dequant()),
                                  np.asarray(plain.dequant()))
    slabs = np.split(np.asarray(sh.codes), count, axis=dim)
    for slab, ws in zip(slabs, np.split(w, count, axis=dim)):
        indep = packing.pack_linear(ws, bits, s, 6, 0.02)
        np.testing.assert_array_equal(slab, np.asarray(indep.codes))
    assert sh.per_shard_bytes * count == sh.packed_bytes
    assert plain.per_shard_bytes == plain.packed_bytes


def test_sharded_nib4_layout_not_w4_eligible():
    """A per-shard re-broken nib4 layout (odd per-shard rows) must not
    feed the w4 kernel, which consumes the PLAIN byte stream — it falls
    back to the unpack-based int8 route; plain packing stays w4-eligible."""
    r = np.random.default_rng(0)
    w = r.normal(size=(12, 8)).astype(np.float32)
    sharded = packing.pack_linear(w, 4, np.float32(0.05), 6, 0.02,
                                  shard_dim=0, shard_count=4)
    assert sharded.sharded_layout()
    assert dispatch.kernel_eligible("bsd,de->bse", sharded) == "pallas-int8"
    plain = packing.pack_linear(w, 4, np.float32(0.05), 6, 0.02)
    assert not plain.sharded_layout()
    assert dispatch.kernel_eligible("bsd,de->bse", plain) == "pallas-w4"


def test_planes_route_from_the_layout():
    """A plain 2-D planes leaf whose bytes tile takes the bit-plane kernel;
    one whose K or N does not tile, or whose row-parallel per-shard packing
    regroups the plane blocks, unpacks in XLA for the int8 kernel; a
    per-channel scale takes no kernel."""
    r = np.random.default_rng(0)
    s = np.float32(0.05)

    def eligible(shape, bits=5, **kw):
        w = r.normal(size=shape).astype(np.float32)
        pl = packing.pack_linear(w, bits, s, 6, 0.02, **kw)
        assert pl.layout == "planes"
        return dispatch.kernel_eligible("bsd,de->bse", pl)

    assert eligible((256, 128)) == "pallas-planes"
    assert eligible((512, 384), bits=3) == "pallas-planes"
    assert eligible((24, 128)) == "pallas-int8"           # K tiles no plane
    assert eligible((256, 72)) == "pallas-int8"           # N under 128 lanes
    assert eligible((256, 128), shard_dim=1, shard_count=2) == "pallas-planes"
    assert eligible((512, 128), shard_dim=0, shard_count=2) == "pallas-int8"
    assert eligible((256, 128), per_channel=True) is None


def test_pack_linear_intermediates_fit_yi9b_width():
    """Packing a 3-bit 4096 x 11008 weight (yi-9b's MLP) builds no integer
    intermediate larger than twice the weight's int8 size: the planes codec
    works in bytes, never on a per-code bit matrix. The float quantize of
    the float32 weight (w / s, clip, round) is one elementwise pass as large
    as its input, which XLA fuses; it is bounded by the input's size."""
    K, N = 4096, 11008
    w = jax.ShapeDtypeStruct((K, N), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda w: packing.pack_linear(w, 3, 0.05, 8, 0.02))(w)

    def outvars(jx):
        for eqn in jx.eqns:
            yield from eqn.outvars
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from outvars(sub)

    int8_size = K * N
    sizes = []
    for v in outvars(jaxpr.jaxpr):
        a = v.aval
        nbytes = int(np.prod(a.shape)) * a.dtype.itemsize
        sizes.append(nbytes)
        if jnp.issubdtype(a.dtype, jnp.floating):
            assert nbytes <= 4 * int8_size, (a, nbytes)
        else:
            assert nbytes <= 2 * int8_size, (a, nbytes)
    assert max(sizes) >= int8_size      # the walk saw the full-size values
    codes = jaxpr.out_avals[0]
    assert codes.shape == (3 * K // 8, N) and codes.dtype == jnp.uint8


class _Mesh2x4:
    axis_names = ("data", "model")
    shape = {"data": 2, "model": 4}


def test_packed_specs_shard_every_code_leaf():
    """Under a 2x4 mesh every packed projection of the demo arch shards its
    codes (no replicated sub-byte storage left), column-parallel scales
    shard with their out dim, and the per-shard accounting lands on
    policy.size_bytes / tp exactly (all dims divide -> zero padding)."""
    from repro.dist import sharding
    from repro.models.quant_layers import QuantContext as QC

    cfg = smoke_config("limpq-demo")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    ctx = QC.make(cfg.bits, cfg.quant_act_signed, compute_dtype=jnp.float32)
    ql = lm.enumerate_qlayers(cfg)
    policy = MPQPolicy.uniform(ql, 4)
    axes = sharding.make_axes_for(cfg, _Mesh2x4(), shard_seq=False)
    assert axes.tp_size == 4
    sess = QuantizedSession(cfg, params, policy, ctx, axes, kv_quant="int8")

    specs = sharding.packed_specs(cfg, sess.params, axes)
    leaves = {}
    for path, s in jax.tree_util.tree_flatten_with_path(
            specs, is_leaf=packing.is_packed)[0]:
        if packing.is_packed(s):
            leaves["/".join(str(getattr(k, "key", k)) for k in path)] = s
    assert len(leaves) == len(ql)
    for name, s in leaves.items():
        assert any(e is not None for e in tuple(s.codes)), (name, s.codes)
    # column-parallel scale shards, row-parallel scale replicates
    # PartitionSpec normalizes a singleton axis tuple (("model",) reads as
    # "model"), so compare specs, not their raw entry tuples
    assert P(*leaves["sites/000/wq"].scale) == P(("model",))
    assert P(*leaves["sites/000/wo"].scale) == P(None)
    # per-shard accounting: every leaf sharded 4-ways, dims all divide
    per_shard = sess.packed_bytes(per_shard=True)
    assert per_shard * 4 == sess.packed_bytes()
    assert per_shard == policy.size_bytes(ql, per_shard=4)
    assert policy.size_bytes(ql) == policy.size_bytes(ql, per_shard=1)


def test_pack_linear_per_channel_reduces_error():
    r = np.random.default_rng(0)
    w = (r.normal(size=(16, 8)) * r.uniform(0.1, 4.0, size=8)).astype(
        np.float32)
    s = np.float32(np.abs(w).max() / 7.0)
    pt = packing.pack_linear(w, 4, s, 8, 0.02)
    pc = packing.pack_linear(w, 4, s, 8, 0.02, per_channel=True)
    err_pt = float(jnp.sum((pt.dequant() - w) ** 2))
    err_pc = float(jnp.sum((pc.dequant() - w) ** 2))
    assert pc.per_channel and not pt.per_channel
    assert err_pc <= err_pt


# ===========================================================================
# kernels + dispatch
# ===========================================================================
def test_quant_matmul_w4_packed_equivalence():
    """Interpret-mode quant_matmul on nib4-packed int4 weights == the fp
    reference, including non-tile-aligned shapes."""
    from repro.kernels import ops
    r = np.random.default_rng(3)
    M, K, N = 5, 26, 11
    xq = r.integers(-31, 32, size=(M, K)).astype(np.int8)
    wq = r.integers(-8, 8, size=(K, N)).astype(np.int8)
    wp = packing.pack_nib4(wq)
    s_x, s_w = np.float32(0.05), np.float32(0.07)
    out = ops.quant_matmul_w4(jnp.asarray(xq), wp, s_x, s_w, k=K,
                              blocks=(8, 8, 8))
    ref = (xq.astype(np.float32) * s_x) @ (wq.astype(np.float32) * s_w)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("bits", [3, 5, 6])
def test_quant_matmul_planes_equivalence(bits):
    """Interpret-mode bit-plane kernel == quant_matmul on the unpacked
    codes, bitwise, with a K that fills no whole 8-row group and K tiles
    regrouped by bit position."""
    from repro.kernels import ops
    r = np.random.default_rng(bits)
    qmin, qmax = bit_range(bits, True)
    s_x, s_w = np.float32(0.05), np.float32(0.07)
    for M, K, N, blocks in ((5, 26, 16, (8, 8, 8)),
                            (3, 40, 24, (8, 8, 16))):
        xq = jnp.asarray(r.integers(-127, 128, size=(M, K)), jnp.int8)
        wq = r.integers(qmin, qmax + 1, size=(K, N)).astype(np.int8)
        wp = packing.pack_planes(wq, bits)
        out = ops.quant_matmul_planes(xq, wp, s_x, s_w, bits=bits,
                                      blocks=blocks)
        ref = ops.quant_matmul(xq, jnp.asarray(wq), s_x, s_w, blocks=blocks)
        assert out.shape == (M, N)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.fixture(scope="module")
def qctx():
    return QuantContext.make((2, 3, 4, 5, 6), True,
                             compute_dtype=jnp.float32)


def _packed_from_bank(p, w_idx, a_idx, bits, qctx):
    from repro.runtime.session import effective_weight_scale
    wb = int(bits[w_idx])
    s_w = effective_weight_scale(p["s_w"], w_idx, p["w"].size, wb)
    return packing.pack_linear(p["w"], wb, s_w, int(bits[a_idx]),
                               jnp.asarray(p["s_a"])[a_idx])


def test_dispatch_fallback_bitwise_exact(qctx):
    """dequant-then-fp dispatch == the fake-quant qeinsum, bitwise, for
    both weight orientations (column- and row-parallel eqns)."""
    bits = (2, 3, 4, 5, 6)
    r = np.random.default_rng(1)
    x = jnp.asarray(r.normal(size=(2, 5, 13)), jnp.float32)
    for eqn_in, w_idx, a_idx in (
            ("bsd,de->bse", 2, 3),           # kernel-form orientation
            ("bse,ed->bsd", 1, 0)):          # row-parallel: fallback-only
        p = qdense_init(jax.random.PRNGKey(w_idx), 13, 9, bits) \
            if eqn_in.startswith("bsd") else \
            qdense_init(jax.random.PRNGKey(w_idx), 5, 13, bits)
        xx = x if eqn_in.startswith("bsd") else \
            jnp.asarray(r.normal(size=(2, 4, 5)), jnp.float32)
        ref = qeinsum(eqn_in, xx, p, {"w": w_idx, "a": a_idx}, qctx)
        pl = _packed_from_bank(p, w_idx, a_idx, bits, qctx)
        got = dispatch.packed_qeinsum(eqn_in, xx, pl, qctx,
                                      impl="dequant-fp")
        assert bool(jnp.all(ref == got)), float(jnp.max(jnp.abs(ref - got)))


def test_dispatch_moe_stacked_fallback(qctx):
    """3-D expert-stacked packed weights (DISTINCT per-expert bank scales,
    the (E,1,1) broadcast form) go through the exact fallback bitwise."""
    from repro.runtime.session import effective_weight_scale
    bits = (2, 3, 4, 5, 6)
    r = np.random.default_rng(2)
    p = qdense_init(jax.random.PRNGKey(9), 7, 5, bits, stacked=(3,))
    p["s_w"] = p["s_w"] * jnp.asarray([1.0, 1.6, 0.5])[:, None]
    p["s_a"] = p["s_a"] * jnp.asarray([1.0, 2.0, 0.7])[:, None]
    x = jnp.asarray(r.normal(size=(3, 4, 7)), jnp.float32)   # (E, T, d)
    ref = qeinsum("etd,edf->etf", x, p, {"w": 1, "a": 2}, qctx)
    s_w = effective_weight_scale(p["s_w"], 1, p["w"].size,
                                 int(bits[1]), w_ndim=3)
    assert s_w.shape == (3, 1, 1)
    pl = packing.pack_linear(p["w"], int(bits[1]), s_w, int(bits[2]),
                             jnp.asarray(p["s_a"])[..., 2])
    assert dispatch.kernel_eligible("etd,edf->etf", pl) is None
    got = dispatch.packed_qeinsum("etd,edf->etf", x, pl, qctx)
    assert bool(jnp.all(ref == got)), float(jnp.max(jnp.abs(ref - got)))


def test_dispatch_kernel_routes_close(qctx):
    """Forced Pallas routes (int8, packed-int4, bit planes) agree with the
    fallback to int32-accumulation tolerance."""
    bits = (2, 3, 4, 5, 6)
    p = qdense_init(jax.random.PRNGKey(5), 16, 12, bits)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(2, 3, 16)),
                    jnp.float32)
    pl = _packed_from_bank(p, 2, 3, bits, qctx)      # 4-bit -> nib4 layout
    assert pl.layout == "nib4"
    assert dispatch.kernel_eligible("bsd,de->bse", pl) == "pallas-w4"
    pl5 = _packed_from_bank(p, 3, 3, bits, qctx)     # 5-bit -> planes layout
    assert pl5.layout == "planes"
    for leaf, impls in ((pl, ("pallas-w4", "pallas-int8")),
                        (pl5, ("pallas-planes", "pallas-int8"))):
        ref = dispatch.packed_qeinsum("bsd,de->bse", x, leaf, qctx,
                                      impl="dequant-fp")
        for impl in impls:
            with dispatch.force_impl(impl):
                got = dispatch.packed_qeinsum("bsd,de->bse", x, leaf, qctx)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=1e-5, atol=1e-5)
    # off-TPU auto-resolution stays on the exact fallback
    assert dispatch.resolve("bsd,de->bse", pl) == "dequant-fp"


# ===========================================================================
# int8 KV cache
# ===========================================================================
def test_kv_quantize_dequantize_matches_fake():
    r = np.random.default_rng(4)
    x = jnp.asarray(r.normal(size=(2, 7, 3, 8)), jnp.float32)
    q, s = qkv.quantize_rows(x)
    assert q.dtype == jnp.int8 and s.shape == (2, 7, 3)
    np.testing.assert_array_equal(np.asarray(qkv.dequantize(q, s)),
                                  np.asarray(qkv.fake_quant_kv(x)))
    assert int(jnp.max(jnp.abs(q.astype(jnp.int32)))) <= 127


@pytest.mark.parametrize("per_slot", [True, False])
def test_decode_attention_int8_equals_fake(per_slot):
    """decode_attention over a QuantKVCache == decode_attention over an fp
    cache holding the fake-quantized values — both position layouts."""
    r = np.random.default_rng(6)
    B, cap, KV, hd, H = 3, 6, 2, 8, 4
    k_rows = jnp.asarray(r.normal(size=(B, cap, KV, hd)), jnp.float32)
    v_rows = jnp.asarray(r.normal(size=(B, cap, KV, hd)), jnp.float32)
    pos0 = jnp.asarray(np.tile(np.arange(cap), (B, 1)) if per_slot
                       else np.arange(cap), jnp.int32)
    kq, ks = qkv.quantize_rows(k_rows)
    vq, vs = qkv.quantize_rows(v_rows)
    qcache = qkv.QuantKVCache(kq, vq, ks, vs, pos0)
    fcache = attn.KVCache(qkv.fake_quant_kv(k_rows),
                          qkv.fake_quant_kv(v_rows), pos0)
    q = jnp.asarray(r.normal(size=(B, 1, H, hd)), jnp.float32)
    k_new = jnp.asarray(r.normal(size=(B, 1, KV, hd)), jnp.float32)
    v_new = jnp.asarray(r.normal(size=(B, 1, KV, hd)), jnp.float32)
    pos = jnp.full((B,), cap - 1, jnp.int32) if per_slot \
        else jnp.asarray(cap - 1, jnp.int32)
    out_q, cache_q = attn.decode_attention(
        q, qcache, k_new, v_new, pos, window=None)
    out_f, cache_f = attn.decode_attention(
        q, fcache, qkv.fake_quant_kv(k_new), qkv.fake_quant_kv(v_new), pos,
        window=None)
    np.testing.assert_array_equal(np.asarray(out_q), np.asarray(out_f))
    assert isinstance(cache_q, qkv.QuantKVCache)
    np.testing.assert_array_equal(np.asarray(cache_q.pos),
                                  np.asarray(cache_f.pos))


def test_quant_cache_state_plumbing():
    """init/per-slot/trim/specs all treat QuantKVCache like KVCache."""
    cfg = smoke_config("limpq-demo")
    st8 = lm.init_decode_state(cfg, 2, 8, per_slot=True, kv_quant="int8")
    caches = [c for c in jax.tree.leaves(
        st8, is_leaf=lambda x: isinstance(x, attn.CACHE_TYPES))
        if isinstance(c, attn.CACHE_TYPES)]
    assert caches and all(isinstance(c, qkv.QuantKVCache) for c in caches)
    # shared-pos prefill state widens to per-slot, and bucketed-prefill
    # trimming invalidates pad rows past the true length
    shared = attn.build_prefill_cache(
        jnp.ones((2, 4, 2, 8)), jnp.ones((2, 4, 2, 8)), 4, 8,
        kv_quant="int8")
    wide = attn.cache_per_slot(shared)
    assert wide.pos.shape == (2, 8)
    trimmed = lm.trim_decode_state(wide, 3)
    assert int(trimmed.pos[0, 3]) == -1 and int(trimmed.pos[0, 2]) == 2
    # slot-axis partition specs shard the code/scale slot dim over data
    from repro.dist import sharding

    class _Mesh:
        axis_names = ("data",)
        shape = {"data": 2}

    axes = sharding.make_axes_for(cfg, _Mesh())
    specs = sharding.decode_state_specs(cfg, st8, axes)
    flat_state = jax.tree_util.tree_flatten_with_path(st8)[0]
    flat_specs = jax.tree.flatten(specs)[0]
    assert len(flat_state) == len(flat_specs)
    for (path, leaf), spec in zip(flat_state, flat_specs):
        entries = tuple(spec) + (None,) * (leaf.ndim - len(tuple(spec)))
        for dim, ax in zip(leaf.shape, entries):
            if ax is not None:
                assert dim % axes.dp_size == 0
        body = str(getattr(path[0], "key", "")) == "body"
        slot_dim = 1 if body else 0
        if leaf.ndim >= 2 + slot_dim:                # per-slot leaf
            assert P(entries[slot_dim]) == P(axes.dp)


# ===========================================================================
# policy schema + validation
# ===========================================================================
def test_policy_json_has_schema_version():
    ql = lm.enumerate_qlayers(smoke_config("limpq-demo"))
    pol = MPQPolicy.uniform(ql, 4)
    d = json.loads(pol.to_json())
    assert d["schema"] == MPQPolicy.SCHEMA_VERSION
    # pre-versioning files (schema absent) still load
    del d["schema"]
    assert MPQPolicy.from_json(json.dumps(d)).w_bits == pol.w_bits


def test_policy_unknown_schema_rejected():
    ql = lm.enumerate_qlayers(smoke_config("limpq-demo"))
    d = json.loads(MPQPolicy.uniform(ql, 4).to_json())
    d["schema"] = MPQPolicy.SCHEMA_VERSION + 1
    with pytest.raises(ValueError, match="schema"):
        MPQPolicy.from_json(json.dumps(d))


def test_policy_stale_layer_names_fail_loudly():
    cfg = smoke_config("limpq-demo")
    ql = lm.enumerate_qlayers(cfg)
    pol = MPQPolicy.uniform(ql, 4)
    stale = MPQPolicy(
        {("X" + n if i == 0 else n): b
         for i, (n, b) in enumerate(pol.w_bits.items())},
        {("X" + n if i == 0 else n): b
         for i, (n, b) in enumerate(pol.a_bits.items())})
    with pytest.raises(ValueError, match="unknown layer names"):
        lm.bits_from_policy(cfg, stale, ql)
    bad_bits = MPQPolicy(dict(pol.w_bits), dict(pol.a_bits))
    bad_bits.w_bits[ql[0].name] = 7          # not in the searched set
    with pytest.raises(ValueError, match="bit-widths"):
        bad_bits.validate(ql, bits=cfg.bits)


# ===========================================================================
# bit-aware roofline + bucketing
# ===========================================================================
def test_decode_step_cost_orders_quantized_below_fp():
    """Pinned ordering: fp16 weights + bf16 KV cost more HBM time than a
    packed policy + int8 KV, and int8 KV alone beats bf16 KV."""
    cfg = smoke_config("limpq-demo")
    ql = lm.enumerate_qlayers(cfg)
    pol = MPQPolicy.uniform(ql, 4)
    fp = roofline.decode_step_cost(cfg, 4, cache_tokens=64,
                                   avg_weight_bits=16.0, kv_bits=16.0)
    kv8 = roofline.decode_step_cost(cfg, 4, cache_tokens=64,
                                    avg_weight_bits=16.0, kv_bits=8.0)
    packed = roofline.decode_step_cost(
        cfg, 4, cache_tokens=64, kv_bits=8.0,
        w_bits_total=pol.size_bytes(ql) * 8.0)
    assert kv8["memory_s"] < fp["memory_s"]
    assert packed["memory_s"] < kv8["memory_s"]
    assert fp["compute_s"] == packed["compute_s"]
    # quantized serving lowers the decode step's memory ceiling, so the
    # "free" compute headroom — and with it the prefill-token budget —
    # shrinks: the scheduler must see the quantized bytes, not fp ones
    c_fp = roofline.suggest_prefill_chunk(cfg, 4, cache_tokens=64,
                                          avg_weight_bits=16.0, kv_bits=16.0)
    c_q = roofline.suggest_prefill_chunk(cfg, 4, cache_tokens=64,
                                         kv_bits=8.0,
                                         w_bits_total=pol.size_bytes(ql) * 8.0)
    assert c_q <= c_fp


def test_bucket_length():
    assert [bucket_length(n) for n in (1, 7, 8, 9, 16, 33)] == \
        [8, 8, 8, 16, 16, 64]
    assert bucket_length(5, min_bucket=2) == 8


# ===========================================================================
# serving session end-to-end
# ===========================================================================
@pytest.fixture(scope="module")
def serving():
    cfg = smoke_config("limpq-demo")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    ctx = QuantContext.make(cfg.bits, cfg.quant_act_signed,
                            compute_dtype=jnp.float32)
    ql = lm.enumerate_qlayers(cfg)
    bits_seq = sorted(int(b) for b in cfg.bits)
    n = len(bits_seq)
    policy = MPQPolicy(
        {q.name: bits_seq[i % n] for i, q in enumerate(ql)},
        {q.name: bits_seq[(i + 1) % n] for i, q in enumerate(ql)},
        meta={"kind": "cyclic-test"})
    r = np.random.default_rng(7)
    reqs = [Request(rid=i, tokens=r.integers(0, 500, size=p).astype(np.int32),
                    max_new=g, arrival=0)
            for i, (p, g) in enumerate([(8, 4), (4, 3), (6, 4)])]
    return dict(cfg=cfg, params=params, ctx=ctx, ql=ql, policy=policy,
                reqs=reqs)


def _run(engine, reqs):
    engine.submit_all(reqs)
    out = engine.run()
    return {r.rid: out[r.rid].tokens for r in reqs}


def test_session_packed_serves_token_identical(serving):
    """The tentpole gate: packed weights + int8 KV + bucketed prefill
    through the engine == the fake-quant lm reference graph, greedy
    token-for-token; HBM bytes match the policy's accounting."""
    s = serving
    sess = QuantizedSession(s["cfg"], s["params"], s["policy"], s["ctx"],
                            mode="packed", kv_quant="int8")
    ecfg = EngineConfig(slots=2, cache_len=16, kv_quant="int8",
                        bucket_prompts=True)
    eng = DecodeEngine(sess.params, s["cfg"], None, s["ctx"], NO_AXES, ecfg,
                       adapter=sess)
    packed_out = _run(eng, s["reqs"])

    bits = lm.bits_from_policy(s["cfg"], s["policy"], s["ql"])
    ref = DecodeEngine(s["params"], s["cfg"], bits, s["ctx"], NO_AXES,
                       EngineConfig(slots=2, cache_len=16, kv_quant="fake"))
    ref_out = _run(ref, s["reqs"])
    assert packed_out == ref_out

    # bucketing bounded the prefill shapes: prompts 8/4/6 -> buckets {8}
    assert eng.stats.prefill_compiles == 1
    assert ref.stats.prefill_compiles == 3

    info = summarize(sess)
    assert abs(info["packed_vs_policy"] - 1.0) <= 0.05
    assert info["compression_vs_fp32"] > 5.0
    assert sess.w_bits_total == pytest.approx(info["policy_bytes"] * 8.0)


def test_session_from_checkpoint_bundle(serving, tmp_path):
    """save_serving_bundle -> QuantizedSession.from_checkpoint restores an
    identical packed model (codes + scales bitwise equal)."""
    from repro import checkpoint as ckpt
    s = serving
    ckpt.save_serving_bundle(str(tmp_path), 3, s["params"], s["policy"])
    sess = QuantizedSession.from_checkpoint(
        str(tmp_path), s["cfg"], ctx=s["ctx"], kv_quant="int8")
    direct = QuantizedSession(s["cfg"], s["params"], s["policy"], s["ctx"],
                              kv_quant="int8")
    for a, b in zip(jax.tree.leaves(sess.params),
                    jax.tree.leaves(direct.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_session_packed_moe_arch_token_identical():
    """Expert-stacked (MoE) packed weights serve token-identically too —
    per-expert bank scales take the (E,1,1) broadcast packing path."""
    cfg = smoke_config("mixtral-8x7b")
    params = lm.init_params(jax.random.PRNGKey(1), cfg)
    ctx = QuantContext.make(cfg.bits, cfg.quant_act_signed,
                            compute_dtype=jnp.float32)
    ql = lm.enumerate_qlayers(cfg)
    bits_seq = sorted(int(b) for b in cfg.bits)
    n = len(bits_seq)
    policy = MPQPolicy(
        {q.name: bits_seq[i % n] for i, q in enumerate(ql)},
        {q.name: bits_seq[(i + 1) % n] for i, q in enumerate(ql)})
    r = np.random.default_rng(11)
    reqs = [Request(rid=i, tokens=r.integers(0, 500, size=p).astype(np.int32),
                    max_new=g, arrival=0)
            for i, (p, g) in enumerate([(6, 3), (4, 3)])]
    sess = QuantizedSession(cfg, params, policy, ctx, mode="packed",
                            kv_quant="int8")
    eng = DecodeEngine(sess.params, cfg, None, ctx, NO_AXES,
                       EngineConfig(slots=2, cache_len=12, kv_quant="int8"),
                       adapter=sess)
    packed_out = _run(eng, reqs)
    bits = lm.bits_from_policy(cfg, policy, ql)
    ref = DecodeEngine(params, cfg, bits, ctx, NO_AXES,
                       EngineConfig(slots=2, cache_len=12, kv_quant="fake"))
    assert packed_out == _run(ref, reqs)


def test_session_rejects_foreign_policy(serving):
    s = serving
    other = smoke_config("rwkv6-7b")     # different layer paths entirely
    foreign = MPQPolicy.uniform(lm.enumerate_qlayers(other), 4)
    with pytest.raises(ValueError, match="does not match"):
        QuantizedSession(s["cfg"], s["params"], foreign, s["ctx"])


def test_from_checkpoint_validates_before_restore(serving, tmp_path):
    """A bundle saved for one arch restored against another must fail with
    the MPQPolicy.validate message (same path as bits_from_policy), not a
    missing-array error from the checkpoint reader."""
    from repro import checkpoint as ckpt
    s = serving
    ckpt.save_serving_bundle(str(tmp_path), 0, s["params"], s["policy"])
    other = smoke_config("rwkv6-7b")
    with pytest.raises(ValueError, match="does not match"):
        QuantizedSession.from_checkpoint(str(tmp_path), other, ctx=s["ctx"])


def test_activation_code_reuse_counts_and_stays_exact(serving):
    """Satellite (ISSUE 4): under a uniform policy wq/wk/wv (and the two
    gate-path MLP inputs) share one quantized activation per site — the
    engine reports the elided quantize ops, and greedy tokens stay
    identical to the per-layer-quantizing fake-quant reference."""
    s = serving
    ql = s["ql"]
    uniform = MPQPolicy.uniform(ql, 4)
    sess = QuantizedSession(s["cfg"], s["params"], uniform, s["ctx"],
                            mode="packed", kv_quant="int8")
    # pack-time tagging grouped projections with equal (a_bits, bank value)
    tagged = [pl.a_group for pl in packing.packed_leaves(sess.params)]
    assert any(tagged)
    eng = DecodeEngine(sess.params, s["cfg"], None, s["ctx"], NO_AXES,
                       EngineConfig(slots=2, cache_len=16, kv_quant="int8"),
                       adapter=sess)
    packed_out = _run(eng, s["reqs"])
    # per compile: wq/wk/wv save 2, mlp_wg+mlp_wi save 1 -> 3 per site
    assert eng.stats.act_quant_reused > 0
    assert eng.stats.act_quant_reused % (3 * len(sess.sites)) == 0

    bits = lm.bits_from_policy(s["cfg"], uniform, ql)
    ref = DecodeEngine(s["params"], s["cfg"], bits, s["ctx"], NO_AXES,
                       EngineConfig(slots=2, cache_len=16, kv_quant="fake"))
    assert packed_out == _run(ref, s["reqs"])


def test_mixed_policy_qkv_never_share_a_group(serving):
    """The cyclic test policy gives wq/wk/wv distinct a_bits — the shared
    hidden state must NOT be reused across them (reuse never crosses
    bit-widths or bank values), so their tags are pairwise distinct."""
    s = serving
    sess = QuantizedSession(s["cfg"], s["params"], s["policy"], s["ctx"],
                            mode="packed", kv_quant="int8")
    for key, sp in sess.params["sites"].items():
        trio = [sp[n].a_group for n in ("wq", "wk", "wv")]
        named = [t for t in trio if t]
        assert len(named) == len(set(named)), (key, trio)


def test_row_einsum_one_row_matches_batched_rows():
    """A one-row projection gives bitwise the row a multi-row projection
    gives: the one-slot decode step and the multi-token verify step write
    the same KV rows."""
    from repro.models.quant_layers import row_einsum

    r = np.random.default_rng(0)
    x = jnp.asarray(r.normal(size=(1, 4, 256)), jnp.float32)
    w = jnp.asarray(r.normal(size=(256, 512)) * 0.1, jnp.float32)
    f = jax.jit(lambda x, w: row_einsum("bsd,de->bse", x, w))
    one, many = f(x[:, :1], w), f(x, w)
    assert one.shape == (1, 1, 512)
    np.testing.assert_array_equal(np.asarray(one[:, 0]),
                                  np.asarray(many[:, 0]))
