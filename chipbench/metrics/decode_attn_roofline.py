"""Share of the roofline of the fused decode attention over the int8 KV
cache: every layer of every decode launch attending the rows its live
slots hold (the ``decode_attn`` class of the architecture's
``decode_need_s``), over the device time of the Pallas ops its
``KERNELS`` name ``decode_attn``."""
import kernels


def read(r):
    return kernels.decode_share(r, "decode_attn")
