"""LSQ quantizer unit + property tests (paper Eq. 1 + Esser et al. grads)."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import quantizer as qz


def test_round_ste_value_and_grad():
    x = jnp.asarray([-1.6, -0.4, 0.4, 1.6])
    assert jnp.allclose(qz.round_ste(x), jnp.round(x))
    g = jax.grad(lambda v: jnp.sum(qz.round_ste(v)))(x)
    assert jnp.allclose(g, 1.0)     # straight-through


def test_bit_range():
    assert qz.bit_range(4, signed=True) == (-8, 7)
    assert qz.bit_range(4, signed=False) == (0, 15)
    assert qz.bit_range(2, signed=True) == (-2, 1)


def test_fake_quant_values():
    v = jnp.asarray([-3.0, -0.26, -0.24, 0.0, 0.26, 3.0])
    s = jnp.asarray(0.5)
    out = qz.fake_quant(v, s, -2, 1)
    # v/s = [-6, -.52, -.48, 0, .52, 6] -> clip [-2,1] -> round -> * s
    np.testing.assert_allclose(out, [-1.0, -0.5, 0.0, 0.0, 0.5, 0.5])


def test_lsq_scale_gradient_matches_formula():
    """d v_q / d s == round(v/s) - v/s inside the clip range, qmin/qmax
    outside (the LSQ vjp), obtained compositionally from the STE pair."""
    v = jnp.asarray([-5.0, -1.3, -0.2, 0.7, 1.9, 5.0])
    s = jnp.asarray(0.6)
    qmin, qmax = -4, 3

    g = jax.jacobian(lambda s_: qz.fake_quant(v, s_, qmin, qmax))(s)
    vs = v / s
    inside = (vs > qmin) & (vs < qmax)
    expected = jnp.where(inside, jnp.round(vs) - vs,
                         jnp.clip(vs, qmin, qmax))
    np.testing.assert_allclose(np.asarray(g), np.asarray(expected), rtol=1e-5)


def test_indexed_bank_selects_and_routes_grad():
    tables = qz.BitTables.make((2, 3, 4), signed=True)
    bank = jnp.asarray([0.5, 0.25, 0.125])
    v = jnp.linspace(-1, 1, 64)

    for idx, b in enumerate((2, 3, 4)):
        out = qz.fake_quant_indexed(v, bank, idx, tables, numel=v.size)
        qmin, qmax = qz.bit_range(b, True)
        ref = qz.fake_quant(v, bank[idx], qmin, qmax)
        np.testing.assert_allclose(out, ref, rtol=1e-6)

    g = jax.grad(lambda b_: jnp.sum(
        qz.fake_quant_indexed(v, b_, 1, tables, numel=v.size)))(bank)
    assert g[1] != 0.0 and g[0] == 0.0 and g[2] == 0.0   # only selected entry


def test_indexed_bank_stacked_moe():
    """(E, n) banks select per-expert scales that broadcast against w."""
    tables = qz.BitTables.make((2, 4), signed=True)
    bank = jnp.asarray([[0.5, 0.25], [1.0, 0.125]])      # E=2, n=2
    w = jnp.ones((2, 3, 3))
    out = qz.fake_quant_indexed(w, bank, 1, tables, numel=w.size)
    np.testing.assert_allclose(out[0], qz.fake_quant(w[0], 0.25, -8, 7))
    np.testing.assert_allclose(out[1], qz.fake_quant(w[1], 0.125, -8, 7))


@settings(max_examples=30, deadline=None)
@given(st.integers(2, 8), st.floats(0.01, 2.0),
       st.lists(st.floats(-10, 10), min_size=1, max_size=50))
def test_property_quant_error_bound(bits, s, vals):
    """|Q(v) - v| <= s/2 for v inside the clip range."""
    qmin, qmax = qz.bit_range(bits, True)
    v = jnp.asarray(vals, jnp.float32)
    out = qz.fake_quant(v, jnp.asarray(s, jnp.float32), qmin, qmax)
    inside = (v / s >= qmin) & (v / s <= qmax)
    err = jnp.abs(out - v)
    assert bool(jnp.all(jnp.where(inside, err <= s / 2 + 1e-5, True)))


@settings(max_examples=30, deadline=None)
@given(st.integers(2, 8), st.floats(0.01, 2.0),
       st.lists(st.floats(-10, 10), min_size=1, max_size=50))
def test_property_idempotent(bits, s, vals):
    """Q(Q(v)) == Q(v)."""
    qmin, qmax = qz.bit_range(bits, True)
    s = jnp.asarray(s, jnp.float32)
    v = jnp.asarray(vals, jnp.float32)
    q1 = qz.fake_quant(v, s, qmin, qmax)
    q2 = qz.fake_quant(q1, s, qmin, qmax)
    np.testing.assert_allclose(q1, q2, atol=1e-5)


def test_init_scales():
    w = jnp.ones((4, 4)) * 2.0
    s = qz.init_scale_from_stats(w, 7)
    np.testing.assert_allclose(s, 2 * 2.0 / np.sqrt(7), rtol=1e-6)
    np.testing.assert_allclose(qz.init_scale_same(4), 0.1 / 4)


def test_grad_scale_is_bitwise_identity_in_value():
    """The LSQ grad-scale wrapper returns x exactly, whatever the factor
    rounds to — two graphs that compute the factor differently (one
    constant-folded, one at run time) still quantize on one grid."""
    r = np.random.default_rng(0)
    x = jnp.asarray(r.uniform(1e-4, 1.0, size=512), jnp.float32)
    for g in (1e-3, 0.0731, 1 / 3.0, 0.999):
        y = qz.grad_scale(x, jnp.float32(g))
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
        dy = jax.grad(lambda v: jnp.sum(qz.grad_scale(v, g)))(x)
        np.testing.assert_allclose(np.asarray(dy), g, rtol=1e-6)
