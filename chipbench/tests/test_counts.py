"""Operation and byte counts checked against hand-worked numbers for one
qwen3-0.6b layer (d 1024, 16/8 heads of 128, d_ff 3072, vocab 151936):
the shared packed-matmul count and the dense architecture's own."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402

import archs  # noqa: E402
import common  # noqa: E402
import counts  # noqa: E402

dense = archs.by_name("dense")


def one_layer():
    raw = common.config_file("qwen3-0.6b")
    return dict(raw, num_hidden_layers=1)


def test_projection_shapes():
    assert dense.proj_shapes(one_layer()) == {
        "wq": (1024, 2048), "wk": (1024, 1024), "wv": (1024, 1024),
        "wo": (2048, 1024), "mlp_wi": (1024, 3072), "mlp_wg": (1024, 3072),
        "mlp_wo": (3072, 1024)}


def test_quant_matmul_counts_packed_bytes_at_policy_bits():
    # wq at one row and 4 bits: 2*1024*2048 int8 ops; 1024*2048/2 weight
    # bytes, 1024 activation codes, 2048 float32 outputs, two scales
    assert counts.quant_matmul(1, 1024, 2048, 4) == (4194304.0, 1057800.0)
    # 3 bits pack 1024*3072*3/8 bytes; 32 rows
    ops, nbytes = counts.quant_matmul(32, 1024, 3072, 3)
    assert ops == 2 * 32 * 1024 * 3072
    assert nbytes == 1179648 + 32768 + 393216 + 8


def test_least_time_of_the_packed_matmuls():
    raw = one_layer()
    pol = {"w_bits": {f"L000.{p}": 4 for p in dense.PROJ}}
    peaks = {"int8_ops": 393e12, "hbm_bytes_per_s": 819e9}
    # one row: every projection is bound by its bytes, 15728640 weights at
    # 4 bits, 1024*5 + 2048 + 3072 activation codes, 4 bytes per output,
    # 8 bytes of scales per projection
    nbytes = 7864320 + (5 * 1024 + 2048 + 3072) + 4 * 12288 + 7 * 8
    assert dense.quant_matmul_least_s(raw, pol, 1, peaks) == \
        pytest.approx(nbytes / 819e9)
    # 4096 rows: mlp_wo (3072 x 1024) is bound by its operations
    o, b = counts.quant_matmul(4096, 3072, 1024, 4)
    assert o / 393e12 > b / 819e9


def test_decode_attention_counts_only_attended_rows():
    # 100 cached rows for one query: QK and PV over 16 heads of 128; each
    # row holds 8 heads of int8 k and v, their float32 step sizes and a
    # position; q and out are 16 x 128 float32
    assert dense.decode_attn(one_layer(), 100, 1) == (819200.0, 227984.0)


def test_flash_forward_is_causal():
    ops, nbytes = dense.flash_fwd(one_layer(), 2048)
    assert ops == 4 * 16 * 128 * (2048 * 2049 // 2)
    assert nbytes == 4 * 2048 * 128 * (32 + 16)


def test_model_flops_per_token():
    raw = one_layer()
    mm = 15728640
    assert dense.matmul_params(raw) == mm
    head = 2 * 1024 * 151936
    assert dense.token_flops(raw, 0) == 2 * mm + head
    assert dense.token_flops(raw, 10) - dense.token_flops(raw, 0) == \
        4 * 16 * 128 * 10
    # a launch of three live slots attending 10, 20 and 30 rows
    assert dense.decode_flops(raw, [10, 20, 30]) == \
        3 * (2 * mm + head) + 4 * 16 * 128 * 60
    assert dense.prefill_flops(raw, 4) == 2 * mm * 4 + 4 * 16 * 128 * 10 \
        + head
