"""Which device ops of a trace belong to which kernel class, and a class's
share of its roofline.

The Pallas kernels reach the trace as ``tpu_custom_call`` ops whose
instruction takes the kernel's name (``pallas_call(name=...)``):
``%decode_attn_quant.28 = f32[...] custom-call(...)``. An architecture
module's ``KERNELS`` maps each kernel name its programs use to a class
(``matmul``, ``decode_attn``, ...), and its ``decode_need_s`` gives each
class's least time for one decode launch. A Pallas op whose name is in no
map is in no class.
"""
from __future__ import annotations

import re

_INSTR = re.compile(r"\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_SUFFIX = re.compile(r"(?:\.(?:\d+|clone))+$")


def custom(op) -> bool:
    return 'custom_call_target="tpu_custom_call"' in op.name


def kernel_name(op):
    """A Pallas op's kernel name (its instruction's name less the suffixes
    XLA numbers it with); None for any other op."""
    if not custom(op):
        return None
    m = _INSTR.match(op.name)
    return _SUFFIX.sub("", m.group(1)) if m else None


def kernel_class(op, kmap=None):
    """The op's kernel class in ``kmap`` (the dense architecture's
    ``KERNELS`` where none is given); None for an op in no class."""
    if kmap is None:
        import archs
        kmap = archs.by_name("dense").KERNELS
    return kmap.get(kernel_name(op))


def quant_matmul(op, kmap=None) -> bool:
    """The packed matmul kernels."""
    return kernel_class(op, kmap) == "matmul"


def attention(op, kmap=None) -> bool:
    """The fused decode attention kernels."""
    return kernel_class(op, kmap) == "decode_attn"


def in_program(op, prefix: str) -> bool:
    return op.module.startswith(prefix)


def by_name(ops, kmap):
    """{(program, kernel name): [class, calls, seconds]} over the Pallas
    ops."""
    out = {}
    for o in ops:
        name = kernel_name(o)
        if name is not None:
            row = out.setdefault((o.module, name), [kmap.get(name), 0, 0.0])
            row[1] += 1
            row[2] += o.dur * 1e-9
    return out


def least_s(ops: float, nbytes: float, peak_ops: float, bw: float) -> float:
    return max(ops / peak_ops, nbytes / bw)


def share(need_s: float, kernel_s: float):
    """A share of the roofline in percent; nothing where no kernel ran."""
    if not kernel_s or not need_s:
        return None
    return 100.0 * need_s / kernel_s


def decode_share(r, cls: str):
    """The share of its roofline of kernel class ``cls`` in the decode
    program: the least time of every decode launch of the window, at each
    live slot's attended rows (``decode_need_s`` of the cell's
    architecture), over the class's device time."""
    kmap = r.arch.KERNELS
    t = r.trace.kernel_s(lambda o: in_program(o, "jit_decode")
                         and kernel_class(o, kmap) == cls)
    need = sum(r.arch.decode_need_s(r.raw, r.policy, lens, r.peaks)
               .get(cls, 0.0) for lens in r.decodes)
    return share(need, t)
