"""Share of the roofline of the packed matmul kernels in the decode
program: the least time of every decode launch's projections at its live
rows (the ``matmul`` class of the architecture's ``decode_need_s``), over
the device time of the Pallas ops its ``KERNELS`` name ``matmul``."""
import kernels


def read(r):
    return kernels.decode_share(r, "matmul")
