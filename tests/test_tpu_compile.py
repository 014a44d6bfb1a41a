"""Compile-only tier: the TPU compiler accepts every kernel, without a chip.

The v5e topology is *described* (``jax.experimental.topologies``), never
attached: programs are lowered and compiled for one of its chips and
nothing runs.  The description lives in a module-scoped fixture that skips
where it cannot be built, so every worker collects the same tests and only
the one running this file loads the TPU compiler.  The persistent compile
cache is off around these compiles: an entry written for a described chip
cannot be read back on this host.

- every kernel entry point of ``test_compiled_parity.CASES`` at its toy
  shapes;
- the main-path programs at real size: Pipeline II (vocab 131,072) and
  Pipeline III (524,288), apply (one grouped kernel) and fit (one fused
  kernel), each over a 65,536-row batch.  Where the planner says grouped /
  fused, the kernel must compile.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from test_compiled_parity import CASE_IDS, CASES, KERNEL_NAMES

REAL_ROWS = 65536


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2 host, with the compile cache off."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), np.dtype(dtype),
                                sharding=sharding)


def _compile(fn, *args, name=None):
    """Compile for the described chip; the Mosaic kernel must be kept, as
    the op ``%<name>.<n>`` where its ``pallas_call`` is named."""
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text  # Mosaic kernel kept
    if name is not None:
        assert re.search(rf"%{name}\.\d+ = .*custom_call_target="
                         r'"tpu_custom_call"', text), name
    return compiled


@pytest.mark.parametrize("case", CASES, ids=CASE_IDS)
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, args = case(interpret=False)
    _compile(fn, *(_on(one_chip, a.shape, a.dtype) for a in args),
             name=KERNEL_NAMES[case.__name__.removeprefix("case_")])


@pytest.mark.parametrize("which,capacity", [("II", 131072), ("III", 524288)])
@pytest.mark.parametrize("phase", ["apply", "fit"])
def test_main_path_compiles_at_real_size(one_chip, which, capacity, phase):
    from repro.core.pipeline import paper_pipeline
    from repro.data import synth
    vocab = ({"small_vocab": capacity} if which == "II"
             else {"large_vocab": capacity})
    cp = paper_pipeline(which, batch_size=REAL_ROWS, **vocab).compile(
        backend="pallas", interpret=False)
    raw = next(synth.dataset_batches("I", rows=8, batch_size=8, seed=0))
    real = lambda cols: {k: _on(one_chip, (REAL_ROWS,) + v.shape[1:], v.dtype)
                         for k, v in cols.items()}
    if phase == "apply":
        assert {r["path"] for r in cp.lowering_report().values()} == {
            "grouped"}
        resolved = {vid: _on(one_chip, (1, capacity), jnp.int32)
                    for vid in cp._resolved_tables()}
        compiled = _compile(cp._apply_fn, {}, {}, resolved,
                            real(cp._raw_columns(raw)),
                            name="etl_apply_group")
    else:
        assert {r["path"] for r in cp.fit_lowering_report().values()} == {
            "fused"}
        compiled = _compile(cp._fit_chunk_fn,
                            real(cp._raw_columns(raw, cp._fit_bufs)),
                            name="etl_fit_build")
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 30
