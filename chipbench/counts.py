"""Operations and bytes that a shared kernel's algorithm needs, from its
shapes. An architecture module (``arch/<arch>.py``) counts its own layers
with these and with counts of its own.

The counts do not depend on which route the program took: a weight counts
its packed bytes at its policy width whether it was unpacked in XLA or in
the kernel.
"""
from __future__ import annotations

import math


def quant_matmul(m: int, k: int, n: int, w_bits: int):
    """(int8 operations, bytes) of one packed matmul: int8 activation codes
    (m, k) times a (k, n) weight at ``w_bits``, float32 out."""
    ops = 2.0 * m * k * n
    nbytes = math.ceil(k * n * w_bits / 8) + m * k + 4 * m * n + 8
    return ops, float(nbytes)
