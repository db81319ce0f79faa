"""Training substrate: optimizers, checkpoint/restart (incl. fault
injection), gradient compression with error feedback, watchdog."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.mnist import SynthDigits
from repro.data.tokens import TokenStream
from repro.models.mlp_mnist import paper_mlp_init, paper_mlp_loss
from repro.training import (GradCompressor, StallDetected, StepWatchdog,
                            TrainConfig, TrainLoop, latest_step,
                            make_optimizer, restore_checkpoint,
                            save_checkpoint)

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------------------
# Optimizers
# ---------------------------------------------------------------------------

def _quad_problem(opt, steps=60):
    """Minimize ||x - target||^2; returns final distance."""
    target = jnp.asarray(np.linspace(-1, 1, 32), jnp.float32)
    params = {"x": jnp.zeros(32, jnp.float32)}
    state = opt.init(params)
    for _ in range(steps):
        grads = {"x": 2 * (params["x"] - target)}
        params, state = opt.update(params, grads, state)
    return float(jnp.linalg.norm(params["x"] - target))


def test_sgd_and_adamw_converge():
    assert _quad_problem(make_optimizer("sgd", lr=0.1)) < 1e-3
    # constant-LR Adam oscillates near the optimum; 0.05 distance on a
    # unit-scale target is converged for this purpose
    assert _quad_problem(make_optimizer("adamw", lr=0.1), 400) < 0.05


def test_adamw_q8_tracks_adamw():
    """Quantized-moment AdamW lands near plain AdamW on a quadratic."""
    d_q8 = _quad_problem(make_optimizer("adamw_q8", lr=0.1), 200)
    d_fp = _quad_problem(make_optimizer("adamw", lr=0.1), 200)
    assert d_q8 < max(10 * d_fp, 0.15), (d_q8, d_fp)


def test_adamw_q8_state_is_uint8():
    opt = make_optimizer("adamw_q8", lr=1e-3)
    params = {"w": jnp.zeros((8, 16), jnp.float32)}
    st = opt.init(params)
    assert st["mu"]["w"]["codes"].dtype == jnp.uint8
    assert st["nu"]["w"]["codes"].dtype == jnp.uint8


# ---------------------------------------------------------------------------
# Checkpointing
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip_and_prune(tmp_path):
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": [jnp.ones(4), {"c": jnp.zeros((2, 2), jnp.bfloat16)}]}
    for step in (10, 20, 30, 40):
        save_checkpoint(str(tmp_path), step, tree, keep=2)
    assert latest_step(str(tmp_path)) == 40
    restored, step, _ = restore_checkpoint(str(tmp_path), tree)
    assert step == 40
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.asarray(tree["a"]))
    assert restored["b"][1]["c"].dtype == jnp.bfloat16
    # pruned to keep=2
    from repro.training import list_checkpoints
    assert list_checkpoints(str(tmp_path)) == [30, 40]


def test_checkpoint_template_mismatch_raises(tmp_path):
    save_checkpoint(str(tmp_path), 1, {"a": jnp.zeros(3)})
    with pytest.raises(ValueError):
        restore_checkpoint(str(tmp_path), {"zz": jnp.zeros(3)})


def test_elastic_restore_new_sharding(tmp_path):
    """Checkpoint saved unsharded restores under a different device layout
    (single CPU device acts as the 'new mesh')."""
    tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
    save_checkpoint(str(tmp_path), 5, tree)
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import AxisType
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    sh = {"w": NamedSharding(mesh, P(None, None))}
    restored, step, _ = restore_checkpoint(str(tmp_path), tree, shardings=sh)
    assert step == 5
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(tree["w"]))


# ---------------------------------------------------------------------------
# Fault tolerance: kill + resume reproduces uninterrupted training
# ---------------------------------------------------------------------------

def _mlp_loop(tmp_path, kill_at, max_steps, seed=0):
    data = SynthDigits(n_train=512, n_test=64, batch_size=32, seed=seed)
    it = iter_batches(data)
    cfg = TrainConfig(max_steps=max_steps, ckpt_dir=str(tmp_path),
                      ckpt_every=5, log_every=1000, kill_at_step=kill_at)
    loop = TrainLoop(
        loss_fn=lambda p, b: (paper_mlp_loss(p, b["x"], b["y"]), {}),
        opt=make_optimizer("sgd", lr=0.5),
        init_params_fn=lambda: paper_mlp_init(jax.random.PRNGKey(seed)),
        data_iter=it, cfg=cfg)
    return loop


def iter_batches(data):
    while True:
        for x, y in data.batches(epochs=1000):
            yield {"x": jnp.asarray(x), "y": jnp.asarray(y)}


def test_kill_and_resume(tmp_path):
    loop = _mlp_loop(tmp_path, kill_at=12, max_steps=20)
    with pytest.raises(KeyboardInterrupt):
        loop.run()
    assert latest_step(str(tmp_path)) == 10  # last periodic ckpt before kill
    # resume: a fresh loop picks up at 10 and finishes
    loop2 = _mlp_loop(tmp_path, kill_at=None, max_steps=20)
    params, hist = loop2.run()
    assert hist[-1]["step"] == 20
    assert latest_step(str(tmp_path)) == 20


def test_loss_decreases_on_synth_mnist(tmp_path):
    loop = _mlp_loop(tmp_path, kill_at=None, max_steps=60, seed=1)
    params, hist = loop.run()
    first = np.mean([h["loss"] for h in hist[:5]])
    last = np.mean([h["loss"] for h in hist[-5:]])
    assert last < first * 0.8, (first, last)


# ---------------------------------------------------------------------------
# Watchdog
# ---------------------------------------------------------------------------

def test_watchdog_flags_straggler():
    wd = StepWatchdog(stall_factor=3.0, warmup=2, min_stall_s=0.0)
    for _ in range(5):
        wd.observe(0.1)
    with pytest.raises(StallDetected):
        wd.observe(1.0)
    assert wd.stalls == 1


# ---------------------------------------------------------------------------
# Gradient compression (error feedback)
# ---------------------------------------------------------------------------

def test_compression_error_feedback_reduces_bias():
    """With EF, the time-average of compressed grads tracks the true grad
    far better than one-shot quantization."""
    comp = GradCompressor("sp2_4", min_size=1)
    rng = np.random.default_rng(0)
    g_true = jnp.asarray(rng.standard_normal((4, 4096)) * 1e-3, jnp.float32)
    ef = comp.init({"g": g_true})
    acc = jnp.zeros_like(g_true)
    N = 24
    for _ in range(N):
        gq, ef = comp.compress({"g": g_true}, ef)
        acc = acc + gq["g"]
    avg_err_ef = float(jnp.linalg.norm(acc / N - g_true)
                       / jnp.linalg.norm(g_true))
    gq1, _ = comp.compress({"g": g_true}, comp.init({"g": g_true}))
    one_shot_err = float(jnp.linalg.norm(gq1["g"] - g_true)
                         / jnp.linalg.norm(g_true))
    assert avg_err_ef < one_shot_err * 0.5, (avg_err_ef, one_shot_err)


def test_compressed_training_still_converges():
    comp = GradCompressor("sp2_8", min_size=1)
    opt = make_optimizer("sgd", lr=0.1)
    target = jnp.asarray(np.linspace(-1, 1, 64), jnp.float32)
    params = {"x": jnp.zeros(64, jnp.float32)}
    state = opt.init(params)
    ef = comp.init(params)
    for _ in range(80):
        grads = {"x": 2 * (params["x"] - target)}
        grads, ef = comp.compress(grads, ef)
        params, state = opt.update(params, grads, state)
    assert float(jnp.linalg.norm(params["x"] - target)) < 0.05
