"""Which device ops of a trace belong to which kernel, and each kernel's
least time on the chip from the counts in ``counts.py``.

The Pallas kernels reach the trace as ``tpu_custom_call`` ops; they are told
apart by their operand and result types in the op's HLO text.
"""
from __future__ import annotations

import re

import counts

_QMM = re.compile(r"custom-call\(s8\[\d+,\d+\]\{[^}]*\}[^,]*, [su]8\[\d+,\d+\]")


def custom(op) -> bool:
    return 'custom_call_target="tpu_custom_call"' in op.name


def quant_matmul(op) -> bool:
    """The int8 and packed-int4 matmul kernels: an int8 (m, k) activation
    operand and a (k, n) or (k/2, n) one-byte weight operand."""
    return custom(op) and bool(_QMM.search(op.name))


def attention(op) -> bool:
    """The attention kernels: every other Pallas kernel in the serving
    programs (decode attention in the decode program, flash forward in the
    prefill program)."""
    return custom(op) and not quant_matmul(op)


def in_program(op, prefix: str) -> bool:
    return op.module.startswith(prefix)


def least_s(ops: float, nbytes: float, peak_ops: float, bw: float) -> float:
    return max(ops / peak_ops, nbytes / bw)


def quant_matmul_least_s(raw, policy, m: int, peaks) -> float:
    """Least time of every searched projection's packed matmul at ``m``
    rows, each at its own roofline."""
    total = 0.0
    shapes = counts.proj_shapes(raw)
    for i in range(raw["num_hidden_layers"]):
        for p in counts.PROJ:
            k, n = shapes[p]
            o, b = counts.quant_matmul(m, k, n,
                                       policy["w_bits"][f"L{i:03d}.{p}"])
            total += least_s(o, b, peaks["int8_ops"],
                             peaks["hbm_bytes_per_s"])
    return total


def share(need_s: float, kernel_s: float):
    """A share of the roofline in percent; nothing where no kernel ran."""
    if not kernel_s or not need_s:
        return None
    return 100.0 * need_s / kernel_s
