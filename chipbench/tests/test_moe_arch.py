"""The MoE architecture module (``arch/moe.py``): a tiny run of the whole
harness on the CPU, its counts worked by hand for two layers of four
experts, and the four readers of the ``deepseek-moe-16b.short-chat`` cell
on a hand-built reduction."""
import json
import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

import tiny  # noqa: E402

import archs  # noqa: E402
import reduce_trace as tr  # noqa: E402
import run as bench_run  # noqa: E402
import scopes  # noqa: E402

moe = archs.by_name("moe")
dense = archs.by_name("dense")


def moe_raw(**size):
    return dict(dict(hidden_size=64, intermediate_size=128,
                     moe_intermediate_size=32, num_hidden_layers=3,
                     first_k_dense_replace=1, n_routed_experts=8,
                     num_experts_per_tok=2, n_shared_experts=2,
                     num_attention_heads=4, num_key_value_heads=4,
                     head_dim=16, vocab_size=256), **size,
                name="tiny-moe", arch="moe", norm_topk_prob=False,
                rope_theta=10000.0, rms_norm_eps=1e-6,
                tie_word_embeddings=False, hidden_act="silu")


def moe_policy(raw, bits=None):
    """Every searched width, spread over the projections as ``tiny.policy``
    spreads them, or ``bits`` for all."""
    widths = (2, 3, 4, 5, 6)
    w, a = {}, {}
    for i in range(raw["num_hidden_layers"]):
        names = (dense.PROJ if i < raw["first_k_dense_replace"]
                 else moe.MOE_PROJ)
        for j, p in enumerate(names):
            w[f"L{i:03d}.{p}"] = bits or widths[(i + j) % 5]
            a[f"L{i:03d}.{p}"] = bits or widths[(2 * i + j + 2) % 5]
    return {"w_bits": w, "a_bits": a, "meta": {}}


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_moe_run_is_correct(trace):
    """The harness's whole tiny run through the MoE module: the program's
    dropless expert layers against the module's plain reference, correct,
    traced and untraced; a traced run reads the expert-load counter and
    names every Pallas kernel."""
    raw = moe_raw()
    out = tiny.run(seconds=2.0, trace=trace, config=raw,
                   policy_text=json.dumps(moe_policy(raw)))
    assert out["correct"], out["checks"]
    if trace:
        assert out["unmapped_kernels"] == {}
        # 4 slots of 2 experts each over 8: 8 (1 - (3/4)^4) = 5.47 if
        # uniform; at least the 2 of one token, at most 8
        hit = out["metrics"]["experts_hit"]["value"]
        assert 2.0 <= hit <= 8.0
        assert out["metrics"]["mfu.short-chat"]["value"] > 0
    else:
        assert {"tokens_per_s", "setup_s", "hbm_in_use_gib"} <= set(
            out["metrics"])


# -- counts worked by hand ---------------------------------------------------
# two layers (one dense, one MoE), d 8, 2 heads of 4 (MHA), dense width 16,
# 4 routed experts of width 4, top 2, 1 shared expert; every weight 4 bits
HAND = dict(hidden_size=8, intermediate_size=16, moe_intermediate_size=4,
            num_hidden_layers=2, first_k_dense_replace=1, n_routed_experts=4,
            num_experts_per_tok=2, n_shared_experts=1,
            num_attention_heads=2, num_key_value_heads=2, head_dim=4,
            vocab_size=32)
PEAKS = {"int8_ops": 1e4, "bf16_flops": 1e3, "hbm_bytes_per_s": 1e3}
LENS = [3, 5]   # two live slots attending 3 and 5 rows


def test_experts_hit_formula():
    raw = moe_raw(**HAND)
    # E (1 - (1 - k/E)^T), E 4, k 2
    assert moe.experts_hit(raw, 1) == 2.0
    assert moe.experts_hit(raw, 2) == 3.0
    assert moe.experts_hit(raw, 3) == 3.5
    assert moe.experts_hit(raw, 60) == pytest.approx(4.0)


def test_decode_need_s_by_hand():
    raw = moe_raw(**HAND)
    need = moe.decode_need_s(raw, moe_policy(raw, bits=4), LENS, PEAKS)
    # expert_matmul: 2 tokens x top 2 = 4 rows through wi, wg (8 -> 4) and
    # wo (4 -> 8): 3 x 2 x 4 x 8 x 4 = 768 int8 ops, 0.0768 s; bytes: 3
    # experts hit x 16 packed bytes x 3 projections = 144, rows in and out
    # 96 + 96 + 144 = 336; 480 bytes, 0.48 s
    assert need["expert_matmul"] == pytest.approx(0.48)
    # matmul, at 2 rows, each 4-bit projection max(ops / 1e4, bytes / 1e3):
    # dense layer: wq wk wv wo 8x8 (32 + 16 + 64 + 8 = 120 B) 0.12 each;
    # mlp_wi, mlp_wg 8x16 (64 + 16 + 128 + 8) 0.216 each; mlp_wo 16x8
    # (64 + 32 + 64 + 8) 0.168: 1.08. MoE layer: attention 0.48; shared
    # wi, wg 8x4 (16 + 16 + 32 + 8) 0.072 each, wo 4x8 (16 + 8 + 64 + 8)
    # 0.096: 0.72
    assert need["matmul"] == pytest.approx(1.08 + 0.72)
    # decode_attn: 8 rows x (16 + 16 + 4) B + 2 queries x 2 x 4 x 8 B = 416
    # B against 256 ops, 0.416 s a layer, 2 layers
    assert need["decode_attn"] == pytest.approx(0.832)


def test_decode_flops_by_hand():
    raw = moe_raw(**HAND)
    # per token: dense layer 4 x 64 + 2 x 128 + 128 = 640 parameters; MoE
    # layer 4 x 64 attention + 3 x 32 shared + 8 x 4 router + 2 experts x
    # 96 = 576; 2 x 1216 + the head 2 x 8 x 32 = 2944; 4 x 2 layers x 2
    # heads x 4 = 64 a row attended
    assert moe.decode_flops(raw, LENS) == 2 * 2944 + 8 * 64


# -- the readers on a hand-built reduction ----------------------------------
HLO = """\
HloModule jit_decode, entry_computation_layout={()->()}

ENTRY %main (p: s32[8]) -> f32[8] {
  %p = s32[8]{0} parameter(0)
  %fusion.1 = s32[8]{0} fusion(%p), kind=kLoop, \
metadata={op_name="jit(decode)/L001/moe_route/dot_general"}
  %quant_matmul_grouped.2 = f32[8]{0} custom-call(%p), \
custom_call_target="tpu_custom_call", \
metadata={op_name="jit(decode)/L001/moe_experts/kernel/pallas_call"}
  %scatter.3 = f32[8]{0} scatter(%p), \
metadata={op_name="jit(decode)/L001/moe_combine/scatter-add"}
  %quant_matmul_w4.4 = f32[8]{0} custom-call(%p), \
custom_call_target="tpu_custom_call", \
metadata={op_name="jit(decode)/L001/shared_expert/kernel/pallas_call"}
  %quant_matmul_w4.5 = f32[8]{0} custom-call(%p), \
custom_call_target="tpu_custom_call", \
metadata={op_name="jit(decode)/L000/mlp_wi/kernel/pallas_call"}
  ROOT %copy.6 = f32[8]{0} copy(%fusion.1)
}
"""


def _op(name, dur_ns, module="jit_decode"):
    custom = ', custom_call_target="tpu_custom_call"' \
        if "quant_matmul" in name else ""
    return tr.Op(f"%{name} = f32[8]{{0}} custom-call(%p){custom}", module,
                 0.0, dur_ns)


def _reading(ops, eng=None):
    raw = moe_raw(**HAND)
    red = tr.Reduction(window=(0.0, 1e9), devices=1, ops=ops)
    return SimpleNamespace(
        raw=raw, policy=moe_policy(raw, bits=4), arch=moe, peaks=dict(
            PEAKS, bf16_flops=1e5), trace=red, window_s=red.window_s,
        decodes=[LENS, LENS], cell=SimpleNamespace(eng=eng))


OPS = [_op("fusion.1", 1e6), _op("quant_matmul_grouped.2", 1.92e9),
       _op("scatter.3", 2e6), _op("quant_matmul_w4.4", 3e6),
       _op("quant_matmul_w4.5", 5e6),
       # the prefill program's ops are in no decode reading
       _op("quant_matmul_grouped.2", 7e9, module="jit_prefill")]


def test_expert_matmul_roofline_reads_the_grouped_kernel():
    r = _reading(OPS)
    got = bench_run.load_reader("expert_matmul_roofline.short-chat")(r)
    # two launches of 0.48 s least time over 1.92 s of the grouped kernel
    assert got == pytest.approx(50.0)
    assert bench_run.load_reader("expert_matmul_roofline.short-chat")(
        _reading(OPS[-1:])) is None


def test_moe_layer_ms_sums_the_five_scopes(monkeypatch):
    monkeypatch.setattr(scopes, "decode_text", lambda cell: HLO)
    got = bench_run.load_reader("moe_layer_ms")(_reading(OPS))
    # route 1 ms, experts 1920 ms, combine 2 ms, shared 3 ms; the dense
    # layer's 5 ms is outside; over 2 launches
    assert got == pytest.approx((1 + 1920 + 2 + 3) / 2)
    assert bench_run.load_reader("moe_layer_ms")(_reading([])) is None


def test_experts_hit_reads_the_engine_counter():
    read = bench_run.load_reader("experts_hit")
    eng = SimpleNamespace(expert_load=lambda: {"launches": 4,
                                               "experts_hit": 3.5})
    assert read(_reading(OPS, eng)) == 3.5
    idle = SimpleNamespace(expert_load=lambda: {"launches": 0,
                                                "experts_hit": None})
    assert read(_reading(OPS, idle)) is None
    # an engine without the counter (the parent program's)
    assert read(_reading(OPS, SimpleNamespace())) is None


def test_mfu_short_chat_is_the_whole_step_share():
    got = bench_run.load_reader("mfu.short-chat")(_reading(OPS))
    # two launches of 6400 FLOPs over 1 s at 1e5 FLOP/s
    assert got == pytest.approx(100.0 * 2 * 6400 / 1e5)
