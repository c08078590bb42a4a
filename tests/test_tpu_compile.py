"""The serving and prefill Pallas kernels compile for a TPU v5e at Qwen3-0.6B
shapes (d_model 1024, d_ff 3072, 16 query / 8 KV heads of 128, 8 decode
slots over a 2080-token cache, 2048-token prefill; the bit-plane matmul at
the searched policy's 3-bit ``mlp_wo``, 5-bit ``wq`` and 6-bit ``wk``).

Interpret mode runs the kernel bodies but not the TPU compiler, which also
refuses blocks that do not tile. These tests compile for a described chip
(no chip attached) and check each program holds its kernel. The topology is
described inside a fixture so that only the worker running this file loads
the TPU compiler.
"""
import functools
import os
import re
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import quant_attention as qa
from repro.kernels import quant_matmul as qmm

SLOTS, D, FF, H, KV, HD = 8, 1024, 3072, 16, 8, 128
CACHE, PAGE, PROMPT, VERIFY = 2080, 128, 2048, 5


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep the cache off meanwhile
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _cases(spec):
    """kernel name -> (function, argument shapes) at Qwen3-0.6B widths."""
    i8, u8, f32, i32 = jnp.int8, jnp.uint8, jnp.float32, jnp.int32
    n_pages = SLOTS * (CACHE // PAGE + 1) + 1
    pages_per_slot = -(-CACHE // PAGE)
    ring_kv = [spec((SLOTS, CACHE, KV, HD), i8), spec((SLOTS, CACHE, KV), f32),
               spec((SLOTS, CACHE, KV, HD), i8), spec((SLOTS, CACHE, KV), f32),
               spec((SLOTS, CACHE), i32)]
    paged_kv = [spec((n_pages, PAGE, KV, HD), i8),
                spec((n_pages, PAGE, KV), f32),
                spec((n_pages, PAGE, KV, HD), i8),
                spec((n_pages, PAGE, KV), f32), spec((n_pages, PAGE), i32),
                spec((SLOTS, pages_per_slot), i32)]
    scalar = spec((), f32)
    return {
        "quant_matmul": (
            qmm.quant_matmul,
            [spec((SLOTS, D), i8), spec((D, FF), i8), scalar, scalar]),
        "quant_matmul_w4": (
            qmm.quant_matmul_w4,
            [spec((SLOTS, D), i8), spec((D // 2, FF), u8), scalar, scalar]),
        **{f"quant_matmul_planes.w{bits}": (
            functools.partial(qmm.quant_matmul_planes, bits=bits),
            [spec((SLOTS, k), i8), spec((bits * k // 8, n), u8), scalar,
             scalar])
           for bits, k, n in ((3, FF, D), (5, D, H * HD), (6, D, KV * HD))},
        "decode_attn_quant": (
            qa.decode_attn_quant,
            [spec((SLOTS, 1, H, HD), f32)] + ring_kv + [spec((SLOTS,), i32)]),
        "decode_attn_quant_paged": (
            qa.decode_attn_quant_paged,
            [spec((SLOTS, 1, H, HD), f32)] + paged_kv
            + [spec((SLOTS,), i32)]),
        "verify_attn_quant": (
            qa.verify_attn_quant,
            [spec((SLOTS, VERIFY, H, HD), f32)] + ring_kv
            + [spec((SLOTS, VERIFY), i32)]),
        "flash_fwd_pallas": (
            lambda q, k, v: fa.flash_fwd_pallas(q, k, v, causal=True),
            [spec((1, PROMPT, KV, H // KV, HD), f32),
             spec((1, PROMPT, KV, HD), f32), spec((1, PROMPT, KV, HD), f32)]),
    }


KERNELS = [
    "quant_matmul", "quant_matmul_w4", "quant_matmul_planes.w3",
    "quant_matmul_planes.w5", "quant_matmul_planes.w6", "decode_attn_quant",
    "decode_attn_quant_paged", "verify_attn_quant", "flash_fwd_pallas"]


def _compile(one_chip, kernel):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = _cases(spec)[kernel]
    return jax.jit(fn).lower(*args).compile()


def _name(kernel):
    """The kernel's ``pallas_call`` name; verify attention unrolls onto the
    decode kernel."""
    return {"verify_attn_quant": "decode_attn_quant"}.get(
        kernel, kernel.split(".")[0])


@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, kernel):
    text = _compile(one_chip, kernel).as_text()
    assert "tpu_custom_call" in text
    # the custom call takes its kernel's name (``pallas_call(name=...)``)
    name = _name(kernel)
    assert re.search(rf"%{name}(\.\d+)? = [^\n]*custom-call\(", text)


@pytest.fixture(scope="module")
def harness_kernels():
    """The chip benchmark's kernel selection (``chipbench/kernels.py``)."""
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chipbench"))
    import kernels
    return kernels


@pytest.mark.parametrize("kernel", [
    "quant_matmul", "quant_matmul_w4", "quant_matmul_planes.w3",
    "quant_matmul_planes.w5", "quant_matmul_planes.w6", "decode_attn_quant",
    "decode_attn_quant_paged"])
def test_harness_tells_packed_matmuls_from_attention(one_chip,
                                                     harness_kernels,
                                                     kernel):
    """A trace names a device op by its HLO text with operand shapes: the
    benchmark counts every packed matmul kernel (an int8 ``(m, k)``
    activation and one 2-D one-byte weight first) in
    ``quant_matmul_roofline`` and every other Pallas call as attention."""
    from jax._src.lib import xla_client as xc

    module = _compile(one_chip, kernel).runtime_executable().hlo_modules()[0]
    printing = xc._xla.HloPrintOptions()
    printing.print_operand_shape = True
    name = _name(kernel)
    ops = [SimpleNamespace(name=line.strip().removeprefix("ROOT "))
           for line in module.to_string(printing).splitlines()
           if re.search(rf"%{name}(\.\d+)? = [^\n]*custom-call\(", line)]
    assert len(ops) == 1 and harness_kernels.custom(ops[0])
    matmul = kernel.startswith("quant_matmul")
    assert harness_kernels.quant_matmul(ops[0]) is matmul
    assert harness_kernels.attention(ops[0]) is not matmul
