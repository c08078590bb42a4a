"""End-to-end system behaviour: the full paper pipeline + drivers."""
import json
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import importance as imp
from repro.core import search
from repro.data import SyntheticLM
from repro.dist.axes import NO_AXES
from repro.models import lm
from repro.models.quant_layers import QuantContext


def test_full_pipeline_improves_over_reversed(tmp_path):
    """The paper's headline mechanics at micro scale: QAT with the
    ILP-searched policy must beat the REVERSED policy (Table-6 ablation
    direction) after identical finetuning."""
    from repro import optim, training
    cfg = get_config("limpq-demo").scaled(n_layers=2, d_model=64, n_heads=2,
                                          n_kv_heads=2, d_ff=256, vocab=256)
    rng = jax.random.PRNGKey(0)
    params = lm.init_params(rng, cfg)
    ctx = QuantContext.make(cfg.bits, cfg.quant_act_signed,
                            compute_dtype=jnp.float32)
    data = SyntheticLM(cfg)
    batches = [{k: jnp.asarray(v) for k, v in data.batch(s, 4, 64).items()}
               for s in range(14)]

    # 1) indicators
    params, _ = imp.train_importance(params, cfg, ctx, batches[:6], lr=0.02)
    ql = lm.enumerate_qlayers(cfg)
    ind = imp.extract_indicators(params, cfg, ql)

    # 2) search fwd + reversed at the same 3-bit-level budget
    budget = search.bitops_budget_for_uniform(ql, 3)
    fwd = search.search_policy(ql, ind, cfg.bits, alpha=1.0,
                               bitops_budget=budget)
    rev = search.search_policy(ql, ind, cfg.bits, alpha=1.0,
                               bitops_budget=budget, reverse=True)

    # 3) identical short finetune under each policy
    def finetune(policy):
        bits = lm.bits_from_policy(cfg, policy, ql)
        opt = optim.adamw(3e-3, clip_norm=1.0)
        step = jax.jit(training.make_train_step(cfg, ctx, opt, bits, NO_AXES,
                                                remat=False))
        p, s = params, opt.init(params)
        for b in batches[6:12]:
            p, s, m = step(p, s, b)
        ev = training.evaluate(p, cfg, ctx, bits, batches[12:])
        return ev["ce"]

    ce_fwd = finetune(fwd.policy)
    ce_rev = finetune(rev.policy)
    assert np.isfinite(ce_fwd) and np.isfinite(ce_rev)
    # direction check (micro-scale, so allow noise): fwd not worse by >2%
    assert ce_fwd <= ce_rev * 1.02


def test_train_driver_runs_and_checkpoints(tmp_path, capsys):
    from repro.launch import train as train_mod
    ck = str(tmp_path / "ck")
    train_mod.main(["--arch", "limpq-demo", "--mode", "qat", "--steps", "4",
                    "--batch", "2", "--seq", "32", "--ckpt-dir", ck,
                    "--ckpt-every", "2"])
    from repro.checkpoint import CheckpointManager
    mgr = CheckpointManager(ck)
    assert mgr.latest_step() == 3


def test_importance_driver_saves_indicators(tmp_path):
    from repro.launch import train as train_mod
    out = str(tmp_path / "ind.json")
    train_mod.main(["--arch", "limpq-demo", "--mode", "importance",
                    "--steps", "2", "--batch", "2", "--seq", "32",
                    "--save-indicators", out])
    with open(out) as f:
        ind = json.load(f)
    cfg = get_config("limpq-demo")
    assert len(ind) == len(lm.enumerate_qlayers(cfg))
    first = next(iter(ind.values()))
    assert len(first["w"]) == cfg.n_bits


def test_serve_driver_runs(capsys):
    from repro.launch import serve as serve_mod
    serve_mod.main(["--arch", "limpq-demo", "--batch", "2",
                    "--prompt-len", "16", "--gen", "4"])
    out = capsys.readouterr().out
    assert "prefill" in out and "int8 quant_matmul" in out
    err = float(out.rsplit("max_err=", 1)[1])
    assert err < 1e-4


def test_compile_cache_dir_follows_env(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code; without
    it the cache lands at the fixed <repo>/.jax_cache."""
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.enable() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable()
        assert path.endswith("/.jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_serve_check_gates_kernel_routes(tmp_path, capsys):
    """With the Pallas matmul route taken (interpreted here), --check
    proves token identity with every route forced to dequant-fp and holds
    the kernel-route logits to the drift bound."""
    from repro.launch import serve as serve_mod
    from repro.runtime import dispatch

    pol = str(tmp_path / "p.json")
    serve_mod.main(["--smoke", "--write-demo-policy", pol])
    with dispatch.force_impl("pallas-int8"):
        eng, _ = serve_mod.main(["--smoke", "--policy", pol, "--requests",
                                 "2", "--gen", "4"])
    out = capsys.readouterr().out
    assert serve_mod.kernel_routes(eng.metrics) == ["dispatch.route.pallas-int8"]
    assert "routes forced to dequant-fp" in out
    assert "kernel ops within" in out
    assert "kernel-route logit drift within" in out


def test_serve_check_gates_paged_kernel_routes(tmp_path, capsys):
    """The paged layout with speculation on the kernel routes (interpreted
    here): --check holds the paged decode and verify kernels to dequant-fp
    op by op, proves token identity on the paged layout with routes forced
    to dequant-fp, and bounds the drift of the logits the paged engine
    recorded while decoding token at a time."""
    from repro.launch import serve as serve_mod
    from repro.runtime import dispatch

    pol = str(tmp_path / "p.json")
    serve_mod.main(["--smoke", "--write-demo-policy", pol])
    with dispatch.force_impl("pallas-int8"):
        eng, _ = serve_mod.main([
            "--smoke", "--policy", pol, "--requests", "2", "--gen", "6",
            "--kv-layout", "paged", "--decode-attn", "fused-interpret",
            "--speculate", "2"])
    out = capsys.readouterr().out
    assert eng.ecfg.kv_layout == "paged" and eng.stats.spec_rounds
    assert "decode_attn.fused-interpret.paged=" in out
    assert "verify_attn.fused-interpret.paged=" in out
    assert "routes forced to dequant-fp" in out
    assert "kernel-route logit drift within" in out


def test_ref_scorer_margin_counts_near_ties():
    """Teacher-forced along a run's own greedy tokens the reference ranks
    every token first; a token swapped for the reference's second choice
    shows up as one tie step whose margin is the top-two gap."""
    from repro.launch import serve as serve_mod
    from repro.launch.engine import DecodeEngine, EngineConfig, LMAdapter

    cfg = serve_mod.smoke_config("limpq-demo")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    ctx = QuantContext.make(cfg.bits, cfg.quant_act_signed,
                            compute_dtype=jnp.float32)
    bits = lm.bits_from_policy(cfg, serve_mod.demo_mixed_policy(cfg))
    reqs = serve_mod.build_requests(SyntheticLM(cfg), 2, 16, 5)
    eng = DecodeEngine(params, cfg, bits, ctx, NO_AXES, EngineConfig(
        slots=2, cache_len=21, policy="fixed", record_logits=True))
    eng.submit_all(reqs)
    out = eng.run()
    scorer = serve_mod.RefScorer(LMAdapter(cfg, bits, ctx), params, 21)
    own = scorer.drift(reqs, out)
    assert own["margin"] == 0.0 and own["ties"] == 0
    assert own["first_divergence"] is None
    assert own["ratio"] <= 1e-5

    last = out[0].logits[-1]
    second = int(np.argsort(last)[-2])
    out[0].tokens[-1] = second
    swapped = scorer.drift(reqs, out)
    gap = (last.max() - last[second]) / swapped["scale"]
    assert swapped["ties"] == 1
    assert swapped["first_divergence"] == len(out[0].tokens) - 1
    np.testing.assert_allclose(swapped["margin"], gap, rtol=1e-4, atol=1e-6)


def test_serve_check_admits_near_ties_where_graphs_round_apart(
        tmp_path, capsys, monkeypatch):
    """Where the backend rounds two equivalent graphs apart (tie bound
    above 0), a dequant-fp token that differs from the reference engine's
    passes only as a near-tie of the reference teacher-forced along the
    dequant-fp tokens; on the CPU the bound is 0 and the gate is exact."""
    from repro.launch import serve as serve_mod

    assert serve_mod.tie_bound() == 0.0
    pol = str(tmp_path / "p.json")
    serve_mod.main(["--smoke", "--write-demo-policy", pol])

    class SwapLastToken(serve_mod.DecodeEngine):
        # the reference engine (the one given fake-quant bits) ends every
        # request on another token
        def run(self):
            out = super().run()
            if getattr(self.adapter, "bits", None) is not None:
                for c in out.values():
                    c.tokens[-1] += 1
            return out

    monkeypatch.setattr(serve_mod, "DecodeEngine", SwapLastToken)
    argv = ["--smoke", "--policy", pol, "--requests", "2", "--gen", "4"]
    with pytest.raises(SystemExit, match="diverged from the fake-quant"):
        serve_mod.main(argv)
    monkeypatch.setattr(serve_mod, "tie_bound", lambda: 0.15)
    serve_mod.main(argv)
    out = capsys.readouterr().out
    assert "reference teacher-forced along them: 0 near-tie step(s)" in out
    assert "identical with the fake-quant reference graph but for " \
           "near-ties" in out
