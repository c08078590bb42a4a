"""A fault planted in the fused decode-attention kernel must fail
``serve --check``: the end-to-end logit drift cannot tell such a fault from
the cascade of activation-code flips through 28 random-weight layers, so
``runtime.parity`` holds each kernel to its dequant-fp route op by op."""
import dataclasses

import jax.numpy as jnp
import pytest

from repro.kernels import quant_attention as qa

BLOCK = 8   # kv block of the faulted kernel: the smoke cache spans 3


def _k_scale_block(q, kc, ks, vc, vs, pos, qp):
    # the second K-scale block read from the first's index
    return q, kc, ks.at[:, BLOCK:2 * BLOCK].set(ks[:, :BLOCK]), vc, vs, pos, qp


def _mask_shift(q, kc, ks, vc, vs, pos, qp):
    # the causal mask one position short: the newest row drops out
    return q, kc, ks, vc, vs, pos, qp - 1


def _pos_block_swap(q, kc, ks, vc, vs, pos, qp):
    # the first two position blocks swapped
    pos = jnp.concatenate(
        [pos[:, BLOCK:2 * BLOCK], pos[:, :BLOCK], pos[:, 2 * BLOCK:]], axis=1)
    return q, kc, ks, vc, vs, pos, qp


@pytest.mark.parametrize("fault", [_k_scale_block, _mask_shift,
                                   _pos_block_swap],
                         ids=["k-scale-block", "mask-shift", "pos-block-swap"])
def test_serve_check_fails_on_planted_decode_kernel_fault(fault, tmp_path,
                                                          monkeypatch):
    from repro import configs
    from repro.launch import serve as serve_mod
    from repro.runtime import dispatch

    smoke = configs.smoke_config
    # the smoke width of qwen3-0.6b at its full 28 layers
    monkeypatch.setattr(serve_mod, "smoke_config", lambda name: dataclasses
                        .replace(smoke(name), n_layers=28))
    kernel = qa.decode_attn_quant

    def faulty(*args, window=None, interpret=False, **_):
        return kernel(*fault(*args), window=window, kv_block=BLOCK,
                      interpret=interpret)

    monkeypatch.setattr(qa, "decode_attn_quant", faulty)
    pol = str(tmp_path / "p.json")
    serve_mod.demo_mixed_policy(
        serve_mod.smoke_config("qwen3-0.6b")).save(pol)
    with dispatch.force_impl("pallas-int8"), \
            pytest.raises(SystemExit, match="disagree with dequant-fp"):
        serve_mod.main(["--arch", "qwen3-0.6b", "--smoke", "--policy", pol,
                        "--requests", "2", "--gen", "8",
                        "--decode-attn", "fused-interpret"])
