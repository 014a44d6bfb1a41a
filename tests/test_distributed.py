"""Distribution: sharding rules, hlo_cost analyzer, multi-device subprocess.

The 8-device tests run in a subprocess so the 1-device default of the rest of
the suite is untouched (jax locks device count at first init).
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed import hlo_cost
from repro.distributed.sharding import param_specs, batch_specs, cache_specs

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_subprocess(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    # the child never reaches for an accelerator the parent may hold
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# ---------------- hlo_cost analyzer ----------------

def test_analyzer_matches_xla_on_straightline():
    c = jax.jit(lambda a, b: a @ b).lower(
        jax.ShapeDtypeStruct((256, 512), jnp.float32),
        jax.ShapeDtypeStruct((512, 1024), jnp.float32)).compile()
    r = hlo_cost.analyze(c.as_text())
    xla = c.cost_analysis()
    assert r["flops"] == xla["flops"]
    assert abs(r["bytes_accessed"] - xla["bytes accessed"]) / xla["bytes accessed"] < 0.1


def test_analyzer_multiplies_loop_trip_counts():
    def f(x):
        y, _ = jax.lax.scan(lambda c, _: (c @ c, None), x, None, length=10)
        return y
    c = jax.jit(f).lower(jax.ShapeDtypeStruct((128, 128), jnp.float32)).compile()
    r = hlo_cost.analyze(c.as_text())
    assert r["flops"] >= 10 * 2 * 128 ** 3  # XLA's own counts body ONCE
    assert c.cost_analysis()["flops"] < r["flops"]


# ---------------- sharding rules ----------------

def _mk_mesh():
    return jax.sharding.Mesh(
        np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))


def test_param_specs_stacked_layers():
    mesh = _mk_mesh()
    tree = {"blocks": {"attn": {"wq": jax.ShapeDtypeStruct((4, 64, 64),
                                                           jnp.float32)}}}
    spec = param_specs(tree, mesh)
    s = spec["blocks"]["attn"]["wq"]
    assert len(s) == 3  # stacked leading dim handled


def test_batch_specs_rows():
    mesh = _mk_mesh()
    spec = batch_specs({"tokens": jax.ShapeDtypeStruct((8, 16), jnp.int32)},
                       mesh)
    assert len(spec["tokens"]) == 2


def test_cache_specs_layouts():
    mesh = _mk_mesh()
    tree = {"blocks": {"k": jax.ShapeDtypeStruct((2, 4, 32, 8, 16),
                                                 jnp.bfloat16),
                       "pos": jax.ShapeDtypeStruct((32,), jnp.int32)},
            "ssm": jax.ShapeDtypeStruct((2, 4, 8, 16, 32), jnp.float32)}
    spec = cache_specs(tree, mesh)
    assert len(spec["blocks"]["k"]) == 5
    assert all(x is None for x in spec["blocks"]["pos"])


# ---------------- multi-device subprocess ----------------

@pytest.mark.slow
def test_sharded_train_step_runs_on_8_devices():
    out = run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_host_mesh
        from repro.distributed import sharding as shd
        from repro.configs.registry import get_reduced
        from repro.configs.base import ShapeCfg, TrainConfig
        from repro.models.api import build_model, random_batch, input_specs
        from repro.training.train_loop import (TrainState, make_train_step,
                                               jit_train_step)
        assert len(jax.devices()) == 8
        mesh = make_host_mesh(model_axis=2)  # 4 x 2
        shd.set_active_mesh(mesh)
        cfg = get_reduced("llama3_2_3b")
        model = build_model(cfg)
        tcfg = TrainConfig(lr=1e-3, microbatch=2, fsdp=True)
        state_shapes = jax.eval_shape(
            lambda: TrainState.create(model.init(jax.random.key(0)), tcfg))
        shape = ShapeCfg("t", 32, 8, "train")
        step_fn, spec = jit_train_step(
            make_train_step(model.loss, tcfg), mesh, state_shapes,
            input_specs(cfg, shape))
        with mesh:
            state = TrainState.create(model.init(jax.random.key(0)), tcfg)
            batch = random_batch(cfg, shape)
            l0 = None
            for i in range(8):
                state, m = step_fn(state, batch)
                if l0 is None: l0 = float(m["loss"])
            assert float(m["loss"]) < l0
        print("OK8", l0, float(m["loss"]))
    """)
    assert "OK8" in out


@pytest.mark.slow
def test_elastic_checkpoint_reshard_1_to_8_devices():
    """Checkpoint written on 1 device restores onto an 8-device mesh."""
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        # write on the CURRENT (1-device) process
        from repro.configs.base import TrainConfig
        from repro.configs.registry import get_reduced
        from repro.models.api import build_model
        from repro.training import checkpoint as ck
        from repro.training.train_loop import TrainState
        cfg = get_reduced("llama3_2_3b")
        model = build_model(cfg)
        state = TrainState.create(model.init(jax.random.key(3)),
                                  TrainConfig())
        ck.save(state, d, 42)
        out = run_subprocess(f"""
            import jax, numpy as np
            from jax.sharding import NamedSharding
            from repro.launch.mesh import make_host_mesh
            from repro.distributed import sharding as shd
            from repro.configs.base import TrainConfig
            from repro.configs.registry import get_reduced
            from repro.models.api import build_model
            from repro.training import checkpoint as ck
            from repro.training.train_loop import TrainState
            mesh = make_host_mesh(model_axis=2)
            cfg = get_reduced("llama3_2_3b")
            model = build_model(cfg)
            tcfg = TrainConfig()
            shapes = jax.eval_shape(
                lambda: TrainState.create(model.init(jax.random.key(0)), tcfg))
            pspec = shd.param_specs(shapes.params, mesh)
            ospec = shd.param_specs(shapes.opt, mesh)
            from jax.sharding import PartitionSpec as P
            spec = TrainState(params=pspec, opt=ospec, step=P())
            sh = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), spec,
                is_leaf=lambda x: isinstance(x, P))
            zeros = jax.tree_util.tree_map(
                lambda s: np.zeros(s.shape, s.dtype), shapes)
            st = ck.restore({d!r}, zeros, shardings=sh)
            assert int(st.step) == 0
            ndev = len(set(
                dev for leaf in jax.tree_util.tree_leaves(st.params)
                for dev in leaf.sharding.device_set))
            assert ndev == 8, ndev
            print("ELASTIC_OK", ck.latest_step({d!r}))
        """)
        assert "ELASTIC_OK 42" in out


@pytest.mark.slow
def test_dryrun_cell_on_8_devices():
    """A miniature of the production dry-run on an 8-device host mesh."""
    out = run_subprocess("""
        import jax
        from repro.configs.base import ShapeCfg
        from repro.distributed import sharding as shd, hlo_cost
        from repro.launch.cells import plan_cell
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(model_axis=2)
        shd.set_active_mesh(mesh)
        shape = ShapeCfg("train_tiny", 256, 16, "train")
        plan = plan_cell("mamba2_370m", shape, mesh)
        with mesh:
            lowered = plan.jitted.lower(*plan.abstract_args)
            compiled = lowered.compile()
        r = hlo_cost.analyze(compiled.as_text())
        assert r["flops"] > 0 and r["n_collectives"] > 0
        print("CELL_OK", int(r["n_collectives"]))
    """)
    assert "CELL_OK" in out
