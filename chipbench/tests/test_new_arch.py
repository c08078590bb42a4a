"""An architecture joins the benchmark through new files alone: a module
written into a directory of its own, chosen by the ``arch`` key of a
configuration file, drives the harness's whole run (the program's config,
the weights, the reference, the counts and the readers) with no harness
file edited.

The module covers a variant the program already runs: an ungated GELU MLP
(``mlp_gated=False``, ``act="gelu"``), six searched projections a layer,
with the dense attention and kernels."""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny  # noqa: E402

import archs  # noqa: E402
import kernels  # noqa: E402
import reduce_trace as tr  # noqa: E402

MODULE = '''"""An ungated GELU MLP decoder: the dense attention, then
gelu(h W_i) W_o."""
import dataclasses
import functools

import jax
import jax.numpy as jnp

import archs
import counts
import kernels
import reference
import weights
from reference import Num, proj, rms

dense = archs.by_name("dense")
PROJ = ("wq", "wk", "wv", "wo", "mlp_wi", "mlp_wo")
KERNELS = dict(dense.KERNELS)
CALLS = []


def model_config(raw):
    return dataclasses.replace(dense.model_config(raw), mlp_gated=False)


def layer(key, raw):
    d, _, _, hd, _ = dense.dims(raw)
    shapes = dense.proj_shapes(raw)
    out = {"norm1": {"scale": jnp.ones((d,), jnp.float32)},
           "norm2": {"scale": jnp.ones((d,), jnp.float32)}}
    for k, name in zip(jax.random.split(key, len(PROJ)), PROJ):
        out[name] = weights.projection(k, *shapes[name])
    if raw["qk_norm"]:
        out["q_norm"] = jnp.ones((hd,), jnp.float32)
        out["k_norm"] = jnp.ones((hd,), jnp.float32)
    return out


def model_params(seed, raw):
    return weights.lm_params(seed, raw, layer)


def _layer(x, p, wb, ab, raw, num):
    p = jax.tree.map(lambda a: a.astype(num.dtype), p)
    x = dense.attend(x, p, wb, ab, raw, num)
    h = rms(x, p["norm2"]["scale"], float(raw["rms_norm_eps"]))
    up = proj(h, p["mlp_wi"], wb[4], ab[4], num)
    return x + proj(jax.nn.gelu(up), p["mlp_wo"], wb[5], ab[5], num)


@functools.partial(jax.jit,
                   static_argnames=("raw_items", "precision", "n_out"))
def _gap_rows(seed_key, tokens, start, wb, ab, *, raw_items, precision,
              n_out):
    return reference.lm_rows(seed_key, tokens, start, wb, ab, dict(raw_items),
                             Num(precision), n_out, layer, _layer)


def score(seed, raw, policy, seqs, cap, n_out, controls=()):
    return reference.teacher_forced(_gap_rows, PROJ, seed, raw, policy, seqs,
                                    cap, n_out, controls)


def decode_need_s(raw, policy, lens, peaks):
    CALLS.append(list(lens))
    shapes, mm = dense.proj_shapes(raw), 0.0
    for i in range(raw["num_hidden_layers"]):
        for p in PROJ:
            o, b = counts.quant_matmul(len(lens), *shapes[p],
                                       policy["w_bits"][f"L{i:03d}.{p}"])
            mm += kernels.least_s(o, b, peaks["int8_ops"],
                                  peaks["hbm_bytes_per_s"])
    o, b = dense.decode_attn(raw, sum(lens), len(lens))
    return {"matmul": mm, "decode_attn": raw["num_hidden_layers"]
            * kernels.least_s(o, b, peaks["bf16_flops"],
                              peaks["hbm_bytes_per_s"])}


def decode_flops(raw, lens):
    CALLS.append(list(lens))
    d, H, _, hd, _ = dense.dims(raw)
    shapes = dense.proj_shapes(raw)
    mm = sum(k * n for p, (k, n) in shapes.items() if p in PROJ)
    per_tok = 2 * mm * raw["num_hidden_layers"] + 2 * d * raw["vocab_size"]
    return len(lens) * per_tok + 4 * raw["num_hidden_layers"] * H * hd \\
        * sum(lens)
'''


@pytest.fixture
def gelu(tmp_path, monkeypatch):
    """The module in a temporary ``arch`` directory, and a configuration
    file that names it."""
    (tmp_path / "arch").mkdir()
    (tmp_path / "arch" / "ungated_gelu.py").write_text(MODULE)
    monkeypatch.setattr(archs, "DIRS",
                        [str(tmp_path / "arch")] + archs.DIRS)
    path = tmp_path / "tiny-gelu.json"
    path.write_text(json.dumps(dict(tiny.raw(), name="tiny-gelu",
                                    arch="ungated_gelu", hidden_act="gelu")))
    yield json.loads(path.read_text())
    sys.modules.pop("chipbench_arch_ungated_gelu", None)


def test_a_new_module_is_picked_by_the_arch_key(gelu):
    mod = archs.load(gelu)
    assert archs.load({}) is archs.by_name("dense")
    cfg = mod.model_config(gelu)
    assert (cfg.mlp_gated, cfg.act) == (False, "gelu")
    op = tr.Op('%quant_matmul_planes.4 = f32[8,64]{1,0} custom-call('
               's8[8,128]{1,0} %a), custom_call_target="tpu_custom_call"',
               "jit_decode", 0.0, 1.0)
    assert kernels.kernel_class(op, mod.KERNELS) == "matmul"
    with pytest.raises(ValueError):
        archs.by_name("no_such_arch")


@pytest.mark.parametrize("trace", [0, 1])
def test_the_run_goes_through_the_new_module(gelu, trace):
    policy = tiny.policy(projs=("wq", "wk", "wv", "wo", "mlp_wi", "mlp_wo"))
    out = tiny.run(seconds=2.0, trace=trace, config=gelu,
                   policy_text=policy)
    assert out["correct"], out["checks"]
    assert out["checks"]["gap_max"]["value"] <= tiny.LIMIT
    mod = archs.load(gelu)
    if trace:
        # the counts were read: every decode launch's live slots, by the
        # rooflines' least times and by mfu.decode
        assert mod.CALLS and all(mod.CALLS)
        assert out["metrics"]["mfu.decode"]["value"] > 0
        assert out["unmapped_kernels"] == {}
    else:
        assert {"tokens_per_s", "setup_s"} <= set(out["metrics"])
