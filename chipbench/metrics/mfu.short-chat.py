"""Whole-step utilization of decode: the model FLOPs of every decode
launch of the window (the architecture's ``decode_flops`` at its live
slots' attended rows), over the window's seconds times the bf16 peak."""


def read(r):
    if not r.decodes:
        return None
    flops = sum(r.arch.decode_flops(r.raw, lens) for lens in r.decodes)
    return 100.0 * flops / (r.window_s * r.peaks["bf16_flops"])
