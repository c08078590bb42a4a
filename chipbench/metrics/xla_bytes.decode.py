"""Bytes accessed by one launch of the decode program the engine compiled,
by XLA's cost analysis. A count, not a time."""


def read(r):
    return r.decode_bytes
