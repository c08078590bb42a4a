"""The scope table and the engine span reader, on hand-built
compiled-program text and host spans. (The traced tiny run in
``test_run.py`` loads the reader on the CPU.)"""
import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import reduce_trace as tr  # noqa: E402
import run as bench_run  # noqa: E402
import scopes  # noqa: E402

HLO = """\
HloModule jit_decode, entry_computation_layout={()->()}

%fused_computation (param_0: s32[8]) -> s32[8] {
  %param_0 = s32[8]{0} parameter(0)
  ROOT %shift.3 = s32[8]{0} shift-right-arithmetic(%param_0, %param_0), \
metadata={op_name="jit(decode)/L000/wq/unpack/shift_right_arithmetic"}
}

ENTRY %main (p: s32[8]) -> f32[8] {
  %p = s32[8]{0} parameter(0)
  %reshape.7 = s32[8,3]{1,0} reshape(%p), \
metadata={op_name="jit(decode)/L000/wq/unpack/reshape" stack_frame_id=3}
  %fusion.2 = s32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation, \
metadata={op_name="jit(decode)/L001/mlp_wo/unpack/shift_right_arithmetic"}
  %quant_matmul.1 = f32[8]{0} custom-call(%p), \
custom_call_target="tpu_custom_call", \
metadata={op_name="jit(decode)/L000/wq/kernel/quant_matmul/pallas_call"}
  ROOT %copy.1 = f32[8]{0} copy(%quant_matmul.1)
}
"""

def op(name, module="jit_decode", dur=0.0):
    return tr.Op(f"%{name} = s32[8]{{0}} {name.split('.')[0]}(%p)", module,
                 0.0, dur)


def test_table_maps_every_instruction_to_its_scope():
    t = scopes.op_names(HLO)
    assert t["reshape.7"] == "jit(decode)/L000/wq/unpack/reshape"
    assert t["shift.3"].endswith("L000/wq/unpack/shift_right_arithmetic")
    assert t["copy.1"] == ""
    assert scopes.scope_of(op("fusion.2"), t).startswith(
        "jit(decode)/L001/mlp_wo/unpack")
    assert scopes.scope_of(op("fusion.99"), t) is None
    assert scopes.site("jit(decode)/L001/mlp_wo/unpack/x") == ("L001",
                                                               "mlp_wo")
    assert scopes.site("jit(decode)/lm_head/dot_general") is None
    assert scopes.under("jit(decode)/L000/wq/unpack/x", "unpack")
    assert not scopes.under("jit(decode)/L000/wq/unpacked", "unpack")


def reading(spans):
    red = tr.Reduction(window=(0.0, 1e9), devices=1)
    return SimpleNamespace(trace=red, cell=None, spans=list(spans))


SPANS = [
    # step 1: 100 ms, one admission with a 40 ms launch, one decode with a
    # 30 ms launch, 20 ms of KV drift
    (0, 100, "engine.step"), (0, 2, "engine.schedule"),
    (2, 45, "engine.admit"), (3, 43, "engine.launch"),
    (43, 45, "engine.sample"), (45, 78, "engine.decode"),
    (46, 76, "engine.launch"), (76, 78, "engine.sample"),
    (78, 98, "engine.kv_drift"), (98, 99, "engine.bookkeeping"),
    (99, 100, "engine.monitor"),
    # step 2: 40 ms, a decode with a 35 ms launch
    (200, 240, "engine.step"), (200, 238, "engine.decode"),
    (201, 236, "engine.launch"), (236, 238, "engine.sample"),
    (238, 240, "engine.monitor"),
    # outside the window
    (2000, 2100, "engine.step"),
]
SPANS = sorted((a * 1e6, b * 1e6, n) for a, b, n in SPANS)


def test_step_split_takes_direct_children_and_launches():
    rows = scopes.step_split(SPANS, (0.0, 1e9))
    assert [(d, x) for d, x, _ in rows] == [(100e6, 70e6), (40e6, 35e6)]
    assert rows[0][2] == {"engine.schedule": 2e6, "engine.admit": 43e6,
                          "engine.decode": 33e6, "engine.kv_drift": 20e6,
                          "engine.bookkeeping": 1e6, "engine.monitor": 1e6}
    assert sum(rows[1][2].values()) == 40e6


def test_engine_host_ms_is_a_step_less_its_launches(monkeypatch):
    read = bench_run.load_reader("engine_host_ms")
    monkeypatch.setattr(scopes, "engine_spans", lambda r: r.spans)
    # (100 - 70 + 40 - 35) / 2 steps
    assert read(reading(SPANS)) == pytest.approx(17.5)
    assert read(reading([])) is None

