"""A run's last line carries the cell's metrics: the end-to-end ones with
``--trace 0``, the per-layer ones that the host can read with ``--trace 1``
(on the CPU no device op runs, so the roofline readers return nothing)."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny  # noqa: E402


@pytest.mark.parametrize("trace, names", [
    (0, {"tokens_per_s", "hbm_in_use_gib", "setup_s"}),
    (1, {"itl_p95_ms", "decode_step_ms", "xla_bytes.decode"}),
])
def test_result_line_has_the_cells_metrics(trace, names):
    out = tiny.run(seconds=2.0, trace=trace)
    assert out["correct"], out["checks"]
    assert names <= set(out["metrics"]), out["metrics"]
    assert all(m["value"] is not None for m in out["metrics"].values())
