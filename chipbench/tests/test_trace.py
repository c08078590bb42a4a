"""The reduction from a profiler trace to metrics, on a small trace recorded
on one TPU v5e by ``record_fixture.py``: three launches of the Pallas int8
matmul (256 x 1024 by 1024 x 2048) and of an XLA f32 matmul (512 x 512),
each pair inside a ``bench.step`` span, a 2 ms ``bench.host`` sleep after
each, all inside ``bench.window``."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import reduce_trace as tr  # noqa: E402

FIXTURE = os.path.join(HERE, "fixture.xplane.pb")


class Ev:
    def __init__(self, name, module="jit_f", start=0.0, dur=0.0):
        self.name, self.module, self.start, self.dur = name, module, start, dur


def test_union_merges_overlaps():
    assert tr._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_short_names():
    name = ("%fusion.12 = f32[8,128]{1,0} fusion(f32[8,128]{1,0} %p), "
            "kind=kLoop, calls=%fused_computation")
    assert tr.short(name) == "fusion.12 fusion kLoop"
    name = ('%decode.7 = f32[8,128]{1,0} custom-call(s8[8,128]{1,0} %a), '
            'custom_call_target="tpu_custom_call"')
    assert tr.short(name) == "decode.7 custom-call tpu_custom_call"


@pytest.fixture(scope="module")
def red():
    return tr.reduce(FIXTURE)


def test_clock_shift_pairs_launches_that_agree():
    # the first device program has no host launch left in the trace: the
    # pairing from the end agrees, the one from the start does not
    assert tr.clock_shift([5e6, 10e6, 20e6], [11.5e6, 21.5e6]) == 1.5e6
    # no pairing agrees: the clocks are left alone
    assert tr.clock_shift([5e6, 10e6, 20e6], [6e6, 13e6, 25e6]) == 0.0
    assert tr.clock_shift([], [1.0]) == 0.0


def test_window_and_busy_time(red):
    # the bench.window span: 46059053 ns for 12498670 ns
    assert red.devices == 1
    assert red.window_s == pytest.approx(0.01249867)
    # three launches of four ops each, none overlapping: 14418 + 14 +
    # 1727 + 3116, 14462 + 13 + 1763 + 3236, 14762 + 13 + 1968 + 3116 ns
    assert len(red.ops) == 12
    assert red.busy_s == pytest.approx(58608e-9)


def test_every_launch_lands_inside_its_host_step(red):
    # the device clock is about 1.35 ms behind the host's; once shifted,
    # each Pallas matmul starts inside the bench.step that launched it
    steps = [(46066253, 47660442), (49791913, 51491983),
             (54243143, 55669513)]
    starts = sorted(o.start for o in red.ops if "tpu_custom_call" in o.name)
    assert all(a <= s <= b for s, (a, b) in zip(starts, steps))


def test_kernel_time_is_the_sum_of_its_events(red):
    import kernels

    qmm = [o for o in red.ops if kernels.quant_matmul(o)]
    assert [o.dur for o in qmm] == [14418.0, 14462.0, 14762.0]
    assert red.kernel_s(kernels.quant_matmul) == pytest.approx(43642e-9)
    assert not any(kernels.attention(o) for o in red.ops)


def test_gaps_are_named_by_host_spans(red):
    longest = red.top_gaps(3)
    assert [n for n, _ in longest] == ["bench.host"] * 3
    assert sum(s for _, s in red.gaps) <= red.window_s - red.busy_s + 1e-9
