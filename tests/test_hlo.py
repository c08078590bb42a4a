"""HLO analyzer: trip-count scaling, collective parsing, XLA calibration."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.dist import hlo


def test_scan_vs_unroll_flops_equal():
    def body(x, w):
        return jnp.dot(x, w), None

    def scanned(x, ws):
        return jax.lax.scan(body, x, ws)[0]

    def unrolled(x, ws):
        for i in range(10):
            x = jnp.dot(x, ws[i])
        return x

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((10, 128, 128), jnp.float32)
    cs = hlo.analyze(jax.jit(scanned).lower(x, ws).compile().as_text())
    cu = hlo.analyze(jax.jit(unrolled).lower(x, ws).compile().as_text())
    expect = 10 * 2 * 128 ** 3
    np.testing.assert_allclose(cs.dot_flops, expect)
    np.testing.assert_allclose(cu.dot_flops, expect)
    assert 10 in cs.trip_counts


def test_matches_xla_cost_analysis_on_unrolled():
    """On a while-free graph the analyzer must agree with XLA exactly."""
    def f(x, w1, w2):
        h = jnp.maximum(x @ w1, 0.0)
        return jnp.sum((h @ w2) ** 2)

    args = [jax.ShapeDtypeStruct(s, jnp.float32)
            for s in [(64, 128), (128, 256), (256, 64)]]
    comp = jax.jit(jax.grad(f, argnums=(1, 2))).lower(*args).compile()
    ca = comp.cost_analysis()
    mine = hlo.analyze(comp.as_text())
    np.testing.assert_allclose(mine.flops, ca["flops"], rtol=1e-6)
    # bytes: XLA's fusion choices vary slightly between runs; agreement
    # within 15% calibrates the estimator without pinning the exact plan
    np.testing.assert_allclose(mine.bytes_hbm, ca["bytes accessed"],
                               rtol=0.15)


def test_gqa_einsum_flops():
    def f(q, k):
        return jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                          preferred_element_type=jnp.float32)

    q = jax.ShapeDtypeStruct((2, 64, 4, 2, 32), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((2, 64, 4, 32), jnp.bfloat16)
    c = hlo.analyze(jax.jit(f).lower(q, k).compile().as_text())
    np.testing.assert_allclose(c.dot_flops, 2 * 2 * 4 * 2 * 64 * 64 * 32)


def test_collective_parsing_synthetic():
    """Hand-written HLO with known collectives and replica groups."""
    txt = """
HloModule test

ENTRY %main (p0: f32[1024,256]) -> f32[1024,256] {
  %p0 = f32[1024,256]{1,0} parameter(0)
  %ar = f32[1024,256]{1,0} all-reduce(%p0), replica_groups={{0,1,2,3}}, to_apply=%add
  %ag = f32[4096,256]{1,0} all-gather(%ar), replica_groups={{0,1,2,3}}, dimensions={0}
  %rs = f32[1024,256]{1,0} reduce-scatter(%ag), replica_groups={{0,1,2,3}}, dimensions={0}, to_apply=%add
  ROOT %cp = f32[1024,256]{1,0} collective-permute(%rs), source_target_pairs={{0,1},{1,2}}
}
"""
    c = hlo.analyze(txt)
    B = 1024 * 256 * 4
    assert c.n_collectives == 4
    np.testing.assert_allclose(c.by_collective["all-reduce"], 2 * 0.75 * B)
    np.testing.assert_allclose(c.by_collective["all-gather"], 0.75 * 4 * B)
    np.testing.assert_allclose(c.by_collective["reduce-scatter"],
                               0.75 * 4 * B)
    np.testing.assert_allclose(c.by_collective["collective-permute"], B)


def test_wide_tuple_comment_stripping():
    """/*index=N*/ comments inside wide tuple types must not hide whiles."""
    txt = """
HloModule t

%body (x: (s32[], f32[2,2], f32[2,2], f32[2,2], f32[2,2], f32[2,2], f32[2,2])) -> (s32[], f32[2,2], f32[2,2], f32[2,2], f32[2,2], f32[2,2], f32[2,2]) {
  %x = (s32[], f32[2,2], f32[2,2], f32[2,2], f32[2,2], /*index=5*/f32[2,2], f32[2,2]) parameter(0)
  %g0 = f32[2,2]{1,0} get-tuple-element(%x), index=1
  %g1 = f32[2,2]{1,0} get-tuple-element(%x), index=2
  %d = f32[2,2]{1,0} dot(%g0, %g1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %r = (s32[], f32[2,2], f32[2,2], f32[2,2], f32[2,2], /*index=5*/f32[2,2], f32[2,2]) tuple(%g0)
}

%cond (x: (s32[], f32[2,2], f32[2,2], f32[2,2], f32[2,2], f32[2,2], f32[2,2])) -> pred[] {
  %x2 = (s32[], f32[2,2], f32[2,2], f32[2,2], f32[2,2], /*index=5*/f32[2,2], f32[2,2]) parameter(0)
  ROOT %lt = pred[] constant(false)
}

ENTRY %main (p: (s32[], f32[2,2], f32[2,2], f32[2,2], f32[2,2], f32[2,2], f32[2,2])) -> s32[] {
  %p = (s32[], f32[2,2], f32[2,2], f32[2,2], f32[2,2], /*index=5*/f32[2,2], f32[2,2]) parameter(0)
  %w = (s32[], f32[2,2], f32[2,2], f32[2,2], f32[2,2], /*index=5*/f32[2,2], f32[2,2]) while(%p), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"7"}}
  ROOT %out = s32[] get-tuple-element(%w), index=0
}
"""
    c = hlo.analyze(txt)
    np.testing.assert_allclose(c.dot_flops, 7 * 2 * 2 * 2 * 2)
    assert 7 in c.trip_counts


def test_slice_semantics():
    """dynamic-slice reads the slice, not the whole operand."""
    def f(big, idx):
        return jax.lax.dynamic_slice_in_dim(big, idx, 4, axis=0)

    big = jax.ShapeDtypeStruct((1024, 256), jnp.float32)
    c = hlo.analyze(jax.jit(f).lower(
        big, jax.ShapeDtypeStruct((), jnp.int32)).compile().as_text())
    assert c.bytes_hbm < 3 * 4 * 256 * 4 + 4096   # ~2x slice bytes, not 1MB
