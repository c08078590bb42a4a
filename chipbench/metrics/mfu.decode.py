"""Whole-step utilization of decode: the model FLOPs of every token the
decode launches of the window generated (``counts.decode_flops`` at each
token's context), over the window's seconds times the bf16 peak."""
import counts


def read(r):
    if not r.decodes:
        return None
    d = r.raw
    per_tok = counts.decode_flops(d, 0)
    att = counts.decode_flops(d, 1) - per_tok
    flops = sum(live * per_tok + rows * att for rows, live in r.decodes)
    return 100.0 * flops / (r.window_s * r.peaks["bf16_flops"])
