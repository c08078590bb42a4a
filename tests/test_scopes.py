"""Named scopes on the packed decode program and names on the Pallas calls.

A device op in a profiler trace is tied to its place in the model only
through the compiled program's ``op_name`` metadata: the decode program
scopes each layer as ``L{gidx:03d}``, each projection by its policy key,
and inside each matmul route the unpack, the activation quantization, the
kernel and the fp dequantize; the decode attention, the head and the tied
head's fake-quant have scopes of their own. Every ``pallas_call`` carries
its kernel's public name.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import smoke_config
from repro.core.policy import MPQPolicy
from repro.dist.axes import NO_AXES
from repro.kernels import fake_quant as fq
from repro.kernels import flash_attention as fa
from repro.kernels import quant_attention as qa
from repro.kernels import quant_matmul as qmm
from repro.kernels import rwkv_scan as wkv
from repro.models import lm
from repro.models.quant_layers import QuantContext
from repro.runtime import dispatch
from repro.runtime.session import QuantizedSession

PROJ = ("wq", "wk", "wv", "wo", "mlp_wi", "mlp_wg", "mlp_wo")


@pytest.fixture(scope="module")
def tied():
    cfg = smoke_config("qwen3-0.6b")
    assert cfg.tie_embeddings
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    ctx = QuantContext.make(cfg.bits, cfg.quant_act_signed,
                            compute_dtype=jnp.float32)
    return cfg, params, ctx, lm.enumerate_qlayers(cfg)


def _decode_op_names(tied, bits, route):
    """The scope paths of the decode program, ``jit(decode)/`` taken off:
    every instruction's ``op_name`` in the compiled text, and every
    location of the lowered (not yet optimized) program."""
    cfg, params, ctx, ql = tied
    pol = MPQPolicy({q.name: bits[i % len(bits)] for i, q in enumerate(ql)},
                    {q.name: 6 for q in ql})
    sess = QuantizedSession(cfg, params, pol, ctx, NO_AXES, mode="packed",
                            kv_quant="int8")
    state = sess.init_state(2, 16, jnp.float32)
    with dispatch.force_route("matmul", route), \
            dispatch.force_route("decode_attn", "fused-interpret"):
        lowered = jax.jit(sess.decode).lower(
            sess.params, jnp.zeros((2, 1), jnp.int32),
            jnp.zeros((2,), jnp.int32), state)
    compiled = set(re.findall(r'op_name="jit\(decode\)/([^"]+)"',
                              lowered.compile().as_text()))
    traced = set(re.findall(r'loc\("jit\(decode\)/([^"]+)"',
                            lowered.as_text(debug_info=True)))
    return pol, compiled, traced


def _scoped(names, prefix, scope):
    return any(n.startswith(prefix + "/") and scope in n.split("/")
               for n in names)


@pytest.mark.parametrize("route, bits, inner", [
    # 3- and 6-bit planes and 4-bit nib4 codes all unpack via XLA here
    ("pallas-int8", (3, 4, 6), ("unpack", "act_codes", "kernel")),
    # nib4 bytes are the kernel's operand: nothing unpacks outside it
    ("pallas-w4", (4,), ("act_codes", "kernel")),
    # so are the bit planes of 3-, 5- and 6-bit codes
    ("pallas-planes", (3, 5, 6), ("act_codes", "kernel")),
    ("dequant-fp", (3, 4, 6), ("unpack", "act_codes", "dequant")),
])
def test_decode_ops_name_their_site_and_route_step(tied, route, bits, inner):
    cfg = tied[0]
    pol, names, traced = _decode_op_names(tied, bits, route)
    assert len(pol.w_bits) == cfg.n_layers * len(PROJ)
    for g in range(cfg.n_layers):
        site = f"L{g:03d}"
        assert _scoped(names, site, "decode_attn"), site
        for proj in PROJ:
            for scope in inner:
                if scope == "act_codes" and proj in ("wk", "wv", "mlp_wg"):
                    # the site's activation codes are reused from wq and
                    # mlp_wi (dispatch.act_reuse_scope)
                    continue
                assert _scoped(names, f"{site}/{proj}", scope), \
                    (site, proj, scope)
    if route in ("pallas-w4", "pallas-planes"):
        assert not any("unpack" in n.split("/") for n in names)
    assert any(n.startswith("lm_head/") for n in names)
    # the tied head fake-quantizes the embedding table in every launch;
    # XLA merges it with the lookup's identical fake-quant, which keeps
    # the lookup's scope
    assert _scoped(traced, "lm_head", "head_fake_quant")
    assert any(n.startswith("embed_fake_quant/") for n in names)


def _kernel_calls():
    """Kernel name -> a call of its pallas_call at a small shape."""
    S = jax.ShapeDtypeStruct
    i8, u8, f32, i32 = jnp.int8, jnp.uint8, jnp.float32, jnp.int32
    B, C, KV, H, HD, PAGE, NP = 2, 256, 2, 4, 128, 128, 5
    ring = [S((B, C, KV, HD), i8), S((B, C, KV), f32),
            S((B, C, KV, HD), i8), S((B, C, KV), f32), S((B, C), i32)]
    paged = [S((NP, PAGE, KV, HD), i8), S((NP, PAGE, KV), f32),
             S((NP, PAGE, KV, HD), i8), S((NP, PAGE, KV), f32),
             S((NP, PAGE), i32), S((B, 2), i32)]
    v = S((256, 256), f32)
    return {
        "fake_quant_fwd": (lambda a, s: fq.fake_quant_fwd(
            a, s, -8.0, 7.0, interpret=True), [v, S((), f32)]),
        "fake_quant_bwd": (lambda a, s, g: fq.fake_quant_bwd(
            a, s, g, -8.0, 7.0, interpret=True), [v, S((), f32), v]),
        "flash_fwd_pallas": (lambda q, k, w: fa.flash_fwd_pallas(
            q, k, w, causal=True, q_block=128, kv_block=128, interpret=True),
            [S((1, 256, KV, 2, HD), f32), S((1, 256, KV, HD), f32),
             S((1, 256, KV, HD), f32)]),
        "decode_attn_quant": (
            lambda *a: qa.decode_attn_quant(*a, interpret=True),
            [S((B, 1, H, HD), f32)] + ring + [S((B,), i32)]),
        "decode_attn_quant_paged": (
            lambda *a: qa.decode_attn_quant_paged(*a, interpret=True),
            [S((B, 1, H, HD), f32)] + paged + [S((B,), i32)]),
        "quant_matmul": (
            lambda *a: qmm.quant_matmul(*a, interpret=True),
            [S((8, 256), i8), S((256, 256), i8), S((), f32), S((), f32)]),
        "quant_matmul_w4": (
            lambda *a: qmm.quant_matmul_w4(*a, interpret=True),
            [S((8, 256), i8), S((128, 256), u8), S((), f32), S((), f32)]),
        "quant_matmul_planes": (
            lambda *a: qmm.quant_matmul_planes(*a, bits=3, interpret=True),
            [S((8, 256), i8), S((96, 256), u8), S((), f32), S((), f32)]),
        "wkv_pallas": (
            lambda *a: wkv.wkv_pallas(*a, interpret=True),
            [S((1, 64, 2, 64), f32)] * 4 + [S((2, 64), f32)]),
    }


@pytest.mark.parametrize("kernel", sorted(_kernel_calls()))
def test_every_pallas_call_carries_its_kernel_name(kernel):
    fn, args = _kernel_calls()[kernel]
    jaxpr = jax.make_jaxpr(fn)(*args)
    calls = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    assert calls[0].params["name"] == kernel
