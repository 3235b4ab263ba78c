"""End-to-end loop + serving engine + data pipeline tests."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.config.base import ShapeConfig, TrainConfig
from repro.configs.registry import get_reduced
from repro.data.synthetic import TokenPipeline
from repro.models import io as IO
from repro.models import transformer as T
from repro.models.generate import ServeEngine
from repro.train.loop import StragglerMonitor, TrainLoop


def test_train_loop_loss_decreases(tmp_path):
    cfg = get_reduced("stablelm-3b")
    tcfg = TrainConfig(optimizer="flexa", steps=30, log_every=100,
                       ckpt_dir=str(tmp_path), ckpt_every=10,
                       ckpt_async=False)
    loop = TrainLoop(cfg, tcfg, batch=4, seq_len=64)
    loop.run()
    losses = [m["loss"] for m in loop.metrics_log]
    assert np.mean(losses[-5:]) < np.mean(losses[:5])
    # periodic + final checkpoints exist
    assert loop.ckpt.latest_step() == 30


def test_train_loop_resume_continues(tmp_path):
    cfg = get_reduced("yi-6b")
    tcfg = TrainConfig(optimizer="adamw", lr=1e-3, steps=10, log_every=100,
                       ckpt_dir=str(tmp_path), ckpt_every=5,
                       ckpt_async=False)
    loop1 = TrainLoop(cfg, tcfg, batch=2, seq_len=32)
    loop1.run(steps=5)
    assert loop1.ckpt.latest_step() == 5
    # restart: resumes from step 5, runs to 10
    loop2 = TrainLoop(cfg, tcfg, batch=2, seq_len=32)
    loop2.run(steps=10)
    steps_run = [m["step"] for m in loop2.metrics_log]
    assert steps_run[0] == 6 and steps_run[-1] == 10


def test_straggler_monitor():
    m = StragglerMonitor(factor=2.0)
    for _ in range(10):
        m.observe(0.1)
    assert m.observe(0.5) is True
    assert m.slow_steps == 1
    assert m.observe(0.1) is False


def test_grad_compression_in_loop():
    """topk+EF descends under the γ-scaled feedback carry (γᵏ(1−γᵏ) —
    the fix for the ROADMAP-flagged EF instability; classical unit-scale
    EF made the loss ascend after ~4 steps at this exact configuration)."""
    cfg = get_reduced("stablelm-3b")
    tcfg = TrainConfig(optimizer="flexa", steps=20, log_every=100,
                       grad_compression="topk", grad_topk_frac=0.25)
    loop = TrainLoop(cfg, tcfg, batch=4, seq_len=64)
    loop.run()
    losses = [m["loss"] for m in loop.metrics_log]
    assert np.mean(losses[-5:]) < np.mean(losses[:5])


def test_pipeline_determinism_and_shard_disjointness():
    cfg = get_reduced("yi-6b")
    p1 = TokenPipeline(cfg, batch=4, seq_len=32, seed=7)
    p2 = TokenPipeline(cfg, batch=4, seq_len=32, seed=7)
    np.testing.assert_array_equal(p1(3)["tokens"], p2(3)["tokens"])
    assert not np.array_equal(p1(3)["tokens"], p1(4)["tokens"])
    h0 = TokenPipeline(cfg, 4, 32, seed=7, host_id=0, n_hosts=2)
    h1 = TokenPipeline(cfg, 4, 32, seed=7, host_id=1, n_hosts=2)
    assert not np.array_equal(h0(0)["tokens"], h1(0)["tokens"])
    # labels are next-token shifted
    b = p1(0)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


@pytest.mark.parametrize("arch", ["stablelm-3b", "mamba2-1.3b",
                                  "qwen2-vl-72b"])
def test_serve_engine_matches_forward_greedy(arch):
    """Engine generation == greedy argmax over repeated full forwards."""
    cfg = get_reduced(arch)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, (2, 8)).astype(np.int32)

    eng = ServeEngine(cfg, params, max_len=16)
    res = eng.generate(prompts, max_new_tokens=4)

    # Oracle: re-run full forwards teacher-forced on the ENGINE's tokens;
    # each engine token must be (near-)argmax of the oracle logits — exact
    # argmax equality is too strict at bf16 on random-init near-ties.
    seq = prompts.copy()
    for step in range(4):
        batch = {"tokens": jnp.asarray(seq)}
        if cfg.use_mrope:
            pos = jnp.broadcast_to(
                jnp.arange(seq.shape[1], dtype=jnp.int32)[None],
                (2, seq.shape[1]))
            batch["positions"] = jnp.broadcast_to(
                pos[:, None, :], (2, 3, seq.shape[1]))
        batch["labels"] = batch["tokens"]
        lg, _ = T.forward(cfg, params, batch)
        last = np.asarray(lg[:, -1, :])
        eng_tok = res.tokens[:, step]
        for b in range(2):
            assert last[b, eng_tok[b]] >= last[b].max() - 0.05, \
                (arch, step, b)
        seq = np.concatenate([seq, eng_tok[:, None].astype(np.int32)],
                             axis=1)


def test_serve_engine_encdec():
    cfg = get_reduced("seamless-m4t-large-v2")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, (2, 6)).astype(np.int32)
    enc = rng.standard_normal((2, 6, cfg.d_model)).astype(np.float32)
    eng = ServeEngine(cfg, params, max_len=12)
    res = eng.generate(prompts, max_new_tokens=3,
                       extra_inputs={"enc_embeds": enc})
    assert res.tokens.shape == (2, 3)
    assert (res.tokens >= 0).all() and (res.tokens < cfg.vocab_size).all()
