"""Share of the roofline of the grouped packed-expert matmul in the decode
program: every MoE layer's routed experts at one row per live slot and
expert chosen, reading the packed bytes of the experts the launch hits (the
``expert_matmul`` class of the architecture's ``decode_need_s``), over the
device time of the Pallas ops its ``KERNELS`` name ``expert_matmul``."""
import kernels


def read(r):
    return kernels.decode_share(r, "expert_matmul")
