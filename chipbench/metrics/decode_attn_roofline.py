"""Share of the roofline of the fused decode attention over the int8 KV
cache: every layer of every decode launch attending the rows its live
slots hold (``counts.decode_attn``), over the kernel's device time."""
import counts
import kernels


def read(r):
    t = r.trace.kernel_s(lambda o: kernels.in_program(o, "jit_decode")
                         and kernels.attention(o))
    L = r.raw["num_hidden_layers"]
    need = 0.0
    for rows, live in r.decodes:
        o, b = counts.decode_attn(r.raw, rows, live)
        need += L * kernels.least_s(o, b, r.peaks["bf16_flops"],
                                    r.peaks["hbm_bytes_per_s"])
    return kernels.share(need, t)
