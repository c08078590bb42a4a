"""repro.runtime — policy-driven mixed-precision serving runtime.

Compiles a searched ``MPQPolicy`` into a deployable quantized model:

* ``packing``  — quantize weights onto the searched per-layer grid and
  bit-pack sub-8-bit codes (int4 two-per-byte, int2 four-per-byte, bit
  planes of eight codes a byte otherwise) with per-channel or per-tensor
  scales, plus exact unpack. ``PackedLinear`` is the packed param-tree leaf.
* ``dispatch`` — per-layer kernel registry keyed by bit-width/shape that
  routes packed matmuls to the Pallas int8/int4/bit-plane kernels, falling
  back to an exact dequant-then-fp einsum for shapes the kernels can't
  tile.
* ``kv_cache`` — int8 per-slot KV quantization (per-head write-time
  scales) integrated into ``models.attention.decode_attention`` behind the
  ``QuantContext.kv_quant`` flag.
* ``session``  — ``QuantizedSession``: load a checkpointed policy+params,
  pack once, and expose prefill/decode drop-ins so the continuous-batching
  engine serves the quantized model (imported as ``repro.runtime.session``;
  not imported here to keep ``models`` -> ``runtime.kv_cache`` acyclic).
"""
from repro.runtime import dispatch, kv_cache, packing  # noqa: F401
from repro.runtime.kv_cache import QuantKVCache  # noqa: F401
from repro.runtime.packing import PackedLinear  # noqa: F401
