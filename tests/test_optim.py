"""Optimizer correctness vs handwritten numpy references."""
import jax.numpy as jnp
import numpy as np

from repro import optim


def test_adamw_matches_numpy_reference():
    lr, b1, b2, eps, wd = 0.1, 0.9, 0.999, 1e-8, 0.01
    opt = optim.adamw(lr, b1, b2, eps, weight_decay=wd)
    p = {"w": jnp.asarray([[1.0, -2.0]]), "b": jnp.asarray([0.5])}
    state = opt.init(p)
    m = {k: np.zeros_like(np.asarray(v)) for k, v in p.items()}
    v_ = {k: np.zeros_like(np.asarray(v)) for k, v in p.items()}
    pn = {k: np.asarray(x).copy() for k, x in p.items()}

    rng = np.random.default_rng(0)
    for t in range(1, 6):
        g = {"w": rng.standard_normal((1, 2)).astype(np.float32),
             "b": rng.standard_normal((1,)).astype(np.float32)}
        updates, state = opt.update({k: jnp.asarray(x) for k, x in g.items()},
                                    state, p)
        p = optim.apply_updates(p, updates)
        for k in pn:
            m[k] = b1 * m[k] + (1 - b1) * g[k]
            v_[k] = b2 * v_[k] + (1 - b2) * g[k] ** 2
            u = -lr * (m[k] / (1 - b1 ** t)) / (np.sqrt(v_[k] / (1 - b2 ** t)) + eps)
            if pn[k].ndim >= 2:          # default wd mask: ndim >= 2
                u = u - lr * wd * pn[k]
            pn[k] = pn[k] + u
    for k in pn:
        np.testing.assert_allclose(np.asarray(p[k]), pn[k], rtol=2e-5,
                                   atol=1e-6)


def test_sgd_momentum():
    opt = optim.sgd(0.1, momentum=0.5)
    p = jnp.asarray([1.0])
    state = opt.init(p)
    g = jnp.asarray([1.0])
    u1, state = opt.update(g, state, p)       # mom=1 -> u=-0.1
    u2, state = opt.update(g, state, p)       # mom=1.5 -> u=-0.15
    np.testing.assert_allclose(np.asarray(u1), [-0.1], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(u2), [-0.15], rtol=1e-6)


def test_cosine_warmup_schedule():
    s = optim.cosine_warmup(1.0, warmup_steps=10, total_steps=110)
    np.testing.assert_allclose(float(s(0)), 0.0, atol=1e-7)
    np.testing.assert_allclose(float(s(5)), 0.5, rtol=1e-6)
    np.testing.assert_allclose(float(s(10)), 1.0, rtol=1e-6)
    np.testing.assert_allclose(float(s(110)), 0.0, atol=1e-6)
    mid = float(s(60))
    assert 0.45 < mid < 0.55


def test_clip_by_global_norm():
    tree = {"a": jnp.asarray([3.0]), "b": jnp.asarray([4.0])}
    clipped, gn = optim.clip_by_global_norm(tree, 1.0)
    np.testing.assert_allclose(float(gn), 5.0, rtol=1e-6)
    total = np.sqrt(float(clipped["a"][0]) ** 2 + float(clipped["b"][0]) ** 2)
    np.testing.assert_allclose(total, 1.0, rtol=1e-5)


def test_masked_freeze():
    opt = optim.masked(optim.sgd(0.1, momentum=0.0),
                       lambda path, leaf: optim.path_str(path).endswith("s_w"))
    p = {"layer": {"w": jnp.asarray([1.0]), "s_w": jnp.asarray([1.0])}}
    g = {"layer": {"w": jnp.asarray([1.0]), "s_w": jnp.asarray([1.0])}}
    updates, _ = opt.update(g, opt.init(p), p)
    assert float(updates["layer"]["w"][0]) == 0.0
    assert float(updates["layer"]["s_w"][0]) != 0.0


def test_global_norm_empty_and_scalar():
    assert float(optim.global_norm({})) == 0.0
    np.testing.assert_allclose(float(optim.global_norm(jnp.asarray(3.0))), 3.0)


def test_masked_keeps_no_state_for_frozen_leaves():
    """Frozen leaves carry no optimizer state and stay out of the clipped
    global norm: only the trainable leaf's gradient is clipped."""
    opt = optim.masked(optim.sgd(1.0, momentum=0.9, clip_norm=1.0),
                       lambda path, leaf: optim.path_str(path).endswith("s_w"))
    p = {"w": jnp.ones((4,)), "s_w": jnp.asarray([1.0])}
    state = opt.init(p)
    assert state.momentum["w"] is None
    g = {"w": jnp.full((4,), 100.0), "s_w": jnp.asarray([0.5])}
    updates, _ = opt.update(g, state, p)
    np.testing.assert_array_equal(np.asarray(updates["w"]), 0.0)
    np.testing.assert_allclose(float(updates["s_w"][0]), -0.5, rtol=1e-6)
