"""Training substrate: optimizers, accumulation, compression, checkpointing,
fault tolerance, end-to-end loss decrease."""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ShapeCfg, TrainConfig
from repro.configs.registry import get_reduced
from repro.models.api import build_model, random_batch
from repro.training import checkpoint as ck
from repro.training import fault
from repro.training.grad import (ef_init, microbatched_value_and_grad,
                                 quantize_int8, dequantize_int8,
                                 split_microbatches)
from repro.training.optimizer import clip_by_global_norm, global_norm
from repro.training.train_loop import (LoopConfig, TrainState, make_train_step,
                                       train_loop)

CFG = get_reduced("llama3_2_3b")
MODEL = build_model(CFG)
BATCH = random_batch(CFG, ShapeCfg("t", 32, 8, "train"))


def test_loss_decreases_adamw():
    tcfg = TrainConfig(lr=1e-3)
    state = TrainState.create(MODEL.init(jax.random.key(0)), tcfg)
    step = jax.jit(make_train_step(MODEL.loss, tcfg), donate_argnums=0)
    first = last = None
    for _ in range(25):
        state, m = step(state, BATCH)
        last = float(m["loss"])
        first = first if first is not None else last
    assert last < first * 0.7, (first, last)


def test_loss_decreases_adafactor():
    tcfg = TrainConfig(optimizer="adafactor", lr=1e-3)
    state = TrainState.create(MODEL.init(jax.random.key(0)), tcfg)
    step = jax.jit(make_train_step(MODEL.loss, tcfg), donate_argnums=0)
    first = last = None
    for _ in range(25):
        state, m = step(state, BATCH)
        last = float(m["loss"])
        first = first if first is not None else last
    assert last < first, (first, last)


def test_microbatched_grads_match_full_batch():
    """Accumulated grads == single-shot grads (same loss surface)."""
    params = MODEL.init(jax.random.key(0))
    vg1 = jax.jit(microbatched_value_and_grad(MODEL.loss, 1))
    vg4 = jax.jit(microbatched_value_and_grad(MODEL.loss, 4))
    l1, g1 = vg1(params, BATCH)
    l4, g4 = vg4(params, BATCH)
    np.testing.assert_allclose(float(l1), float(l4), rtol=2e-3)
    for a, b in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g4)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=0.05, atol=5e-3)


def test_split_microbatches_shapes():
    mb = split_microbatches({"x": np.zeros((8, 3))}, 4)
    assert mb["x"].shape == (4, 2, 3)
    with pytest.raises(AssertionError):
        split_microbatches({"x": np.zeros((7, 3))}, 4)


def test_grad_clip():
    tree = {"a": jnp.full((10,), 100.0)}
    clipped, n = clip_by_global_norm(tree, 1.0)
    assert float(global_norm(clipped)) <= 1.0 + 1e-5
    assert float(n) > 100


def test_int8_quantization_roundtrip():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(64, 64)) * 3)
    q, s = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, s)) - np.asarray(x)).max()
    assert err <= float(s) * 0.5 + 1e-6  # half-ulp of the int8 grid


def test_compressed_psum_error_feedback_converges():
    """EF residual carries quantization error: mean of many steps unbiased."""
    from repro.training.grad import compressed_psum_mean
    devs = jax.devices()
    if len(devs) < 1:
        pytest.skip("no devices")
    # single-device shard_map still exercises the code path
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    mesh = Mesh(np.array(devs[:1]), ("d",))
    g = {"w": jnp.asarray(np.random.default_rng(1).normal(size=(32,)) * 0.1,
                          jnp.float32)}
    ef = ef_init(g)
    total = np.zeros(32)
    fn = shard_map(lambda gg, ee: compressed_psum_mean(gg, ee, "d"),
                   mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()))
    acc_err = []
    for i in range(50):
        out, ef = fn(g, ef)
        total += np.asarray(out["w"])
        acc_err.append(np.abs(total / (i + 1) - np.asarray(g["w"])).max())
    assert acc_err[-1] < acc_err[0]  # EF drives the running mean to truth


def test_checkpoint_roundtrip_and_atomicity():
    tcfg = TrainConfig()
    state = TrainState.create(MODEL.init(jax.random.key(0)), tcfg)
    with tempfile.TemporaryDirectory() as d:
        ck.save(state, d, 7)
        assert ck.latest_step(d) == 7
        zeros = jax.tree_util.tree_map(
            lambda x: np.zeros(x.shape, x.dtype), state)
        restored = ck.restore(d, zeros)
        for a, b in zip(jax.tree_util.tree_leaves(state),
                        jax.tree_util.tree_leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # uncommitted dirs are invisible
        os.makedirs(os.path.join(d, "step_00000009"))
        assert ck.latest_step(d) == 7
        # prune keeps newest
        ck.save(state, d, 8)
        ck.save(state, d, 9)
        ck.prune(d, keep=1)
        assert ck.latest_step(d) == 9
        with pytest.raises(FileNotFoundError):
            ck.restore(d, zeros, step=7)


def test_checkpoint_structure_mismatch_rejected():
    with tempfile.TemporaryDirectory() as d:
        ck.save({"a": np.ones(3)}, d, 1)
        with pytest.raises(ValueError):
            ck.restore(d, {"a": np.ones(3), "b": np.ones(2)})


def test_async_checkpointer():
    with tempfile.TemporaryDirectory() as d:
        acp = ck.AsyncCheckpointer()
        acp.save_async({"w": jnp.ones((4, 4))}, d, 3)
        acp.wait()
        assert ck.latest_step(d) == 3


def test_watchdog_fires():
    wd = fault.Watchdog(0.05)
    wd.arm()
    import time
    time.sleep(0.3)
    with pytest.raises(fault.WatchdogTimeout):
        wd.check()
    wd.close()


def test_run_with_restarts():
    attempts = []

    def make_fn():
        def fn():
            attempts.append(1)
            if len(attempts) < 3:
                raise RuntimeError("injected failure")
        return fn

    stats = fault.run_with_restarts(make_fn, max_restarts=5)
    assert stats.restarts == 2 and len(attempts) == 3


def test_restart_resumes_from_checkpoint():
    """Kill training mid-run; restart continues from the last commit."""
    tcfg = TrainConfig(lr=1e-3)
    with tempfile.TemporaryDirectory() as d:
        step_fn = jax.jit(make_train_step(MODEL.loss, tcfg), donate_argnums=0)
        state = TrainState.create(MODEL.init(jax.random.key(0)), tcfg)

        def batches(n):
            for _ in range(n):
                yield BATCH

        # run 10 steps with ckpt every 5, then simulate crash + restore
        state = train_loop(state, step_fn, batches(10),
                           LoopConfig(total_steps=10, ckpt_dir=d,
                                      ckpt_every=5, log_every=0),
                           async_ckpt=False)
        assert ck.latest_step(d) == 10
        zeros = jax.tree_util.tree_map(
            lambda x: np.zeros(x.shape, x.dtype), state)
        restored = ck.restore(d, zeros)
        assert int(restored.step) == 10
        restored = train_loop(restored, step_fn, batches(5),
                              LoopConfig(total_steps=15, ckpt_dir=d,
                                         ckpt_every=5, log_every=0),
                              async_ckpt=False)
        assert int(restored.step) == 15


# ---------------- checkpoint rollover (online service posture) ----------------

def _tiny_state(v=1.0):
    return {"w": np.full((3, 3), v, np.float32)}


def test_prune_interleaved_with_async_saves_keeps_exact():
    """The online rollover pattern — save_async then prune each tick —
    converges to exactly ``keep`` committed checkpoints, newest kept."""
    with tempfile.TemporaryDirectory() as d:
        acp = ck.AsyncCheckpointer()
        for step in range(3, 31, 3):
            acp.save_async(_tiny_state(step), d, step)
            ck.prune(d, keep=2)
        acp.wait()
        ck.prune(d, keep=2)   # the last save commits after its prune
        committed = sorted(
            int(p.split("_")[1]) for p in os.listdir(d)
            if p.startswith("step_")
            and os.path.exists(os.path.join(d, p, "COMMITTED")))
        assert committed == [27, 30]
        assert ck.latest_step(d) == 30
        restored = ck.restore(d, _tiny_state(0.0))
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      _tiny_state(30)["w"])


def test_prune_keep_one_edge():
    with tempfile.TemporaryDirectory() as d:
        for step in (1, 2, 3):
            ck.save(_tiny_state(step), d, step)
        ck.prune(d, keep=1)
        assert ck.latest_step(d) == 3
        assert [p for p in os.listdir(d) if p.startswith("step_")] == \
            ["step_00000003"]


def test_prune_uncommitted_garbage_cannot_displace_committed():
    """Crash-between-save-and-commit edge: an uncommitted ``step_*`` dir
    (newer step number than every committed one) must not count toward the
    keep window — pruning with keep=1 must keep the committed checkpoint
    and delete the garbage, and restore must land on the committed one."""
    with tempfile.TemporaryDirectory() as d:
        ck.save(_tiny_state(7), d, 7)
        # simulate a crash mid-save: step dir exists, no COMMITTED marker
        crash = os.path.join(d, "step_00000009")
        os.makedirs(crash)
        with open(os.path.join(crash, "manifest.json"), "w") as fh:
            fh.write("{}")
        ck.prune(d, keep=1)
        assert not os.path.isdir(crash)          # garbage swept
        assert ck.latest_step(d) == 7            # committed one survived
        restored = ck.restore(d, _tiny_state(0.0))
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      _tiny_state(7)["w"])


def test_compile_cache_fixed_dir_unless_env_set(monkeypatch, tmp_path):
    """Without JAX_COMPILATION_CACHE_DIR the cache goes to one fixed
    directory of the checkout; with it, nothing is set (JAX reads it)."""
    from repro.launch import compile_cache as cc
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert str(cc.CACHE_DIR) == os.path.join(checkout, ".jax_cache")
    before = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert cc.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert cc.enable_compile_cache() == str(cc.CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(cc.CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_lands_in_env_dir(tmp_path):
    """A fresh process honours JAX_COMPILATION_CACHE_DIR: the compiled
    program is written there."""
    import subprocess
    import sys
    code = (
        "import jax\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "enable_compile_cache()\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: x * 2 + 1)(jax.numpy.arange(8)).block_until_ready()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert os.listdir(tmp_path / "cache")
