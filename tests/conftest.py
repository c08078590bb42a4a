"""Shared fixtures. NOTE: no XLA_FLAGS here — smoke tests and benches must
see exactly 1 device; only launch/dryrun.py requests 512 placeholders."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import settings

# Property examples here jit-compile model steps, which takes seconds on the
# first example of a shape: hypothesis's default 200 ms deadline would fail
# them on compile time, not on a wrong result. Examples are drawn from a
# fixed seed and no example database is replayed, so every checkout runs
# the same cases and a pass count does not move between identical trees.
settings.register_profile("repro", deadline=None, derandomize=True,
                          database=None)
settings.load_profile("repro")


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


@pytest.fixture(scope="session")
def nprng():
    return np.random.default_rng(0)


def make_inputs(cfg, rng, B=2, S=32):
    """Correct input dict for any arch family."""
    from repro.models.lm import FRONTEND_DIMS
    ks = jax.random.split(rng, 3)
    if cfg.frontend == "audio_stub":
        return {
            "feats": jax.random.normal(
                ks[0], (B, S, FRONTEND_DIMS["audio_stub"]), jnp.float32),
            "labels": jax.random.randint(ks[1], (B, S), 0, cfg.vocab),
        }
    out = {"tokens": jax.random.randint(ks[0], (B, S), 0, cfg.vocab)}
    if cfg.family == "vlm":
        out["img"] = jax.random.normal(
            ks[2], (B, cfg.n_image_tokens, FRONTEND_DIMS["vision_stub"]),
            jnp.float32)
    return out
