"""End-to-end system behaviour: the full train→checkpoint→restart→serve flow
on the paper's own model (reduced config), plus dry-run machinery smoke."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.configs.base import ParallelConfig
from repro.core import params as P
from repro.data import DataPipeline
from repro.models import transformer as T
from repro.optim import AdamWConfig, init_state
from repro.runtime import PreemptionHandler, run_train_loop
from repro.serving import engine as E
from repro.train import step as TS


def test_end_to_end_train_checkpoint_restart_serve(tmp_path):
    """The paper's deployment story in one test: QAT-train a ternary LM,
    survive a preemption, resume exactly, pack to 2-bit, serve."""
    cfg = get_config("tellme-0.7b", smoke=True)
    specs = T.param_specs(cfg)
    opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=12)
    pcfg = ParallelConfig(microbatches=1, remat="none")
    step = jax.jit(TS.make_train_step(cfg, pcfg, opt_cfg))
    params = P.init_params(specs, jax.random.PRNGKey(0))
    opt = init_state(params, opt_cfg)
    pipe = DataPipeline(cfg.vocab_size, 32, 4)
    ckpt = CheckpointManager(str(tmp_path))

    # phase 1: train until "preempted" at step 4
    pre = PreemptionHandler(install=False)
    rep1 = run_train_loop(
        train_step=step, params=params, opt_state=opt, pipeline=pipe, ckpt=ckpt,
        total_steps=12, checkpoint_every=4, preemption=pre,
        step_hook=lambda s, m: pre.request() if s == 4 else None,
    )
    assert rep1.preempted and ckpt.latest_step() == 4

    # phase 2: restart, restore, finish
    trees, extra = ckpt.restore(4)
    pipe2 = DataPipeline(cfg.vocab_size, 32, 4)
    pipe2.restore(extra["pipeline"])
    rep2 = run_train_loop(
        train_step=step, params=trees["params"], opt_state=trees["opt"],
        pipeline=pipe2, ckpt=ckpt, total_steps=12, start_step=4, checkpoint_every=4,
    )
    assert not rep2.preempted
    assert ckpt.latest_step() == 12

    # phase 3: pack to ternary serving form and decode a few tokens
    trees, _ = ckpt.restore(12)
    packed = T.pack_tree(trees["params"], specs)
    prompts = jnp.asarray(pipe2.next_batch()["tokens"][:2, :16])
    out = E.generate(packed, cfg, prompts, steps=4, mode="packed")
    assert out.tokens.shape == (2, 4)
    assert np.isfinite(np.array(out.prefill_logits)).all()


def test_loss_improves_end_to_end(tmp_path):
    cfg = get_config("tellme-0.7b", smoke=True)
    opt_cfg = AdamWConfig(lr=3e-3, warmup_steps=2, total_steps=20)
    pcfg = ParallelConfig(microbatches=2, remat="none")
    step = jax.jit(TS.make_train_step(cfg, pcfg, opt_cfg))
    params = P.init_params(T.param_specs(cfg), jax.random.PRNGKey(0))
    opt = init_state(params, opt_cfg)
    pipe = DataPipeline(cfg.vocab_size, 64, 4)
    ckpt = CheckpointManager(str(tmp_path))
    rep = run_train_loop(train_step=step, params=params, opt_state=opt,
                         pipeline=pipe, ckpt=ckpt, total_steps=14,
                         checkpoint_every=100)
    assert np.mean(rep.losses[-3:]) < np.mean(rep.losses[:3])


def test_dryrun_cell_smoke_config():
    """The dry-run machinery itself (lower+compile+roofline extraction) on a
    reduced config and the real 1-device mesh."""
    import repro.launch.dryrun as dr
    from repro.launch.mesh import make_local_mesh

    mesh = make_local_mesh()
    step_fn, in_sh, abstract, cfg, pcfg, donate = dr.build_cell(
        "granite-8b", "train_4k", mesh, smoke=True
    )
    with mesh:
        lowered = jax.jit(step_fn, in_shardings=in_sh).lower(*abstract)
        compiled = lowered.compile()
    from repro.analysis import hlo_cost

    cost = hlo_cost.analyze(compiled.as_text())
    assert cost.dot_flops > 0
    assert cost.hbm_bytes > 0


def test_compile_cache_env_var_wins(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: JAX owns it, no other path is set."""
    from repro.launch import compile_cache as CC

    monkeypatch.setenv(CC.ENV_VAR, "/some/where")
    before = jax.config.jax_compilation_cache_dir
    assert CC.use_compile_cache() == "/some/where"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(monkeypatch):
    """Unset: one fixed, git-ignored directory inside the checkout."""
    import pathlib

    from repro.launch import compile_cache as CC

    monkeypatch.delenv(CC.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = CC.use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    root = pathlib.Path(__file__).resolve().parents[1]
    assert path == str(root / ".jax_cache")
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()


def test_skip_reasons():
    import repro.launch.dryrun as dr

    assert dr.skip_reason("llama3-405b", "long_500k") is not None
    assert dr.skip_reason("gemma2-27b", "long_500k") is not None
    assert dr.skip_reason("rwkv6-3b", "long_500k") is None
    assert dr.skip_reason("jamba-v0.1-52b", "long_500k") is None
    assert dr.skip_reason("llama3-405b", "train_4k") is None
