"""The dense architecture module computes what the harness computed before
architecture modules (``parent_dense.py``): the same weights and the same
reference rows, bitwise, at ``tiny.py``'s size; the same least times and
model FLOPs, to 1e-12 relative, on fixed lists of attended lengths at
qwen3-0.6b's size; and the same three device readers on decode ops that
the kernel names and the parent's operand rule file alike."""
import json
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny  # noqa: E402

import archs  # noqa: E402
import common  # noqa: E402
import kernels  # noqa: E402
import parent_dense as parent  # noqa: E402
import reduce_trace as tr  # noqa: E402
import run as bench_run  # noqa: E402

dense = archs.by_name("dense")
SEED = 2**33 + 7
PEAKS = common.read_json(os.path.join("chipbench", "peaks.json"))[
    "TPU v5 lite"]
# live slots' attended rows of one decode launch: one slot, a full and a
# short batch, every slot at the cache's end, lengths spread over the mix
LENS = [[1020], [128] * 32, [1, 2, 3], [4608] * 32,
        list(range(129, 4608, 140))]


def same_bits(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and a.shape == b.shape and \
        a.tobytes() == b.tobytes()


@pytest.mark.parametrize("tied", [True, False])
def test_model_params_bitwise(tied):
    import jax

    raw = tiny.raw(tied)
    new = jax.tree.leaves_with_path(dense.model_params(SEED, raw))
    old = jax.tree.leaves_with_path(parent.model_params(SEED, raw))
    assert [p for p, _ in new] == [p for p, _ in old]
    assert all(same_bits(a, b) for (_, a), (_, b) in zip(new, old))


@pytest.mark.parametrize("tied", [True, False])
def test_reference_rows_bitwise(tied):
    raw = tiny.raw(tied)
    policy = json.loads(tiny.policy())
    rng = np.random.default_rng(5)
    seqs = [(rng.integers(0, 256, p), rng.integers(0, 256, n),
             sorted(rng.choice(n, 2, replace=False)))
            for p, n in ((8, 4), (21, 16), (32, 9))]
    controls = ("bfloat16", "float8_e4m3fn")
    new = dense.score(SEED, raw, policy, seqs, 64, 16, controls)
    old = parent.score(SEED, raw, policy, seqs, 64, 16, controls)
    assert len(new["rows"]) == 6
    for key in ("program", "rows"):
        assert same_bits(new[key], old[key])
    for c in controls:
        assert same_bits(new[c]["gaps"], old[c]["gaps"])
        assert same_bits(new[c]["rows"], old[c]["rows"])


@pytest.mark.parametrize("lens", LENS)
def test_need_and_flops_follow_the_parent_formulas(lens):
    raw = common.config_file("qwen3-0.6b")
    policy = common.read_json(raw["policy"])
    need = dense.decode_need_s(raw, policy, lens, PEAKS)
    assert set(need) == {"matmul", "decode_attn"}
    assert need["matmul"] == pytest.approx(
        parent.quant_matmul_least_s(raw, policy, len(lens), PEAKS),
        rel=1e-12, abs=0)
    o, b = parent.decode_attn(raw, sum(lens), len(lens))
    assert need["decode_attn"] == pytest.approx(
        raw["num_hidden_layers"] * parent.least_s(
            o, b, PEAKS["bf16_flops"], PEAKS["hbm_bytes_per_s"]),
        rel=1e-12, abs=0)
    per_tok = parent.decode_flops(raw, 0)
    att = parent.decode_flops(raw, 1) - per_tok
    assert dense.decode_flops(raw, lens) == pytest.approx(
        len(lens) * per_tok + sum(lens) * att, rel=1e-12, abs=0)


def custom_op(name, operands, dur, module="jit_decode"):
    """A Pallas op as a trace names it: its instruction, result and
    operand types."""
    return tr.Op(f"%{name} = f32[32,1024]{{1,0}} custom-call({operands}), "
                 'custom_call_target="tpu_custom_call"', module, 0.0, dur)


# the decode program's Pallas calls at qwen3-0.6b's widths, as a v5e trace
# prints them, and an XLA op
DECODE_OPS = [
    custom_op("quant_matmul_planes.7", "s8[32,3072]{1,0} %a, "
              "u8[1152,1024]{1,0} %w, u8[1152,1024]{1,0} %w, f32[] %s", 21e3),
    custom_op("quant_matmul_w4.3", "s8[32,1024]{1,0} %a, "
              "u8[512,3072]{1,0} %w, f32[] %s, f32[] %t", 9e3),
    custom_op("quant_matmul.12", "s8[32,1024]{1,0} %a, "
              "s8[1024,2048]{1,0} %w, f32[] %s, f32[] %t", 11e3),
    custom_op("decode_attn_quant.28", "f32[256,2,128]{2,1,0} %q, "
              "s8[256,4608,128]{2,1,0} %k, f32[256,1,4608]{2,1,0} %ks",
              2.02e6),
    custom_op("decode_attn_quant_paged.2", "s32[32,37]{1,0} %t, "
              "f32[256,2,128]{2,1,0} %q", 1.5e6),
    tr.Op("%fusion.3 = f32[32,1024]{1,0} fusion(f32[32,1024]{1,0} %x), "
          "kind=kLoop", "jit_decode", 0.0, 4e4),
]


def test_kernel_names_file_the_decode_ops_as_the_operand_rule_did():
    for o in DECODE_OPS:
        assert kernels.quant_matmul(o) is parent.operand_matmul(o)
        assert kernels.attention(o) is parent.operand_attention(o)
    assert [kernels.kernel_name(o) for o in DECODE_OPS] == [
        "quant_matmul_planes", "quant_matmul_w4", "quant_matmul",
        "decode_attn_quant", "decode_attn_quant_paged", None]


@pytest.mark.parametrize("lens", LENS)
def test_device_readers_match_the_parent(lens):
    raw = common.config_file("qwen3-0.6b")
    policy = common.read_json(raw["policy"])
    red = tr.Reduction(window=(0.0, 51e9), devices=1, ops=DECODE_OPS * 3)
    decodes = [lens, lens[::-1][:7] or lens, lens]
    r = SimpleNamespace(trace=red, raw=raw, policy=policy, peaks=PEAKS,
                        arch=dense, decodes=decodes, window_s=red.window_s)
    old = parent.readers(raw, policy, [(sum(x), len(x)) for x in decodes],
                         PEAKS, red)
    for name, want in old.items():
        assert bench_run.load_reader(name)(r) == pytest.approx(
            want, rel=1e-12, abs=0)
