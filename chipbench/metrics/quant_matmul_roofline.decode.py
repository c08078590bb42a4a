"""Share of the roofline of the packed matmul kernels in the decode
program: the least time of every decode launch's projections at its live
rows (``kernels.quant_matmul_least_s``), over the kernels' device time."""
import kernels


def read(r):
    t = r.trace.kernel_s(lambda o: kernels.in_program(o, "jit_decode")
                         and kernels.quant_matmul(o))
    need = sum(kernels.quant_matmul_least_s(r.raw, r.policy, live, r.peaks)
               for _, live in r.decodes)
    return kernels.share(need, t)
