"""The control comes out as not correct. The configuration states float32,
so the control is bfloat16: the program's own bfloat16 compute path
(``QuantContext.compute_dtype``) driven through the harness and held to its
limits, and the reference put in the program's place in bfloat16 over the
prompts and tokens a sound run served."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny  # noqa: E402


@pytest.mark.parametrize("tied", [True, False])
def test_control_precision_fails_the_limit(tied):
    sound = tiny.run(seconds=2.0, tied=tied, controls=("bfloat16",),
                     size=tiny.WIDER)
    assert sound["correct"], sound["checks"]
    assert sound["controls"]["bfloat16"]["dev_ms"] > tiny.DEV_LIMIT
    assert not sound["controls"]["bfloat16"]["correct"]
    ctl = tiny.run(seconds=2.0, tied=tied, size=tiny.WIDER,
                   compute="bfloat16")
    assert not ctl["correct"], ctl["checks"]
    assert ctl["checks"]["dev_ms"]["value"] > tiny.DEV_LIMIT
