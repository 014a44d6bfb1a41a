"""The output check fails what it must: the control (the reference in
bfloat16 put in the program's place), and each fault a training cell can
have, planted under a whole run of the cell on the CPU at a small size
(the harness's look for a chip skipped).  The limits are the
configuration's own, set on the chip at the cell's size."""

import types

import numpy as np
import pytest

from bench import check, faults, gen, harness
from bench.tests import small

CELL = "criteo-vocab.train"


@pytest.fixture(scope="module")
def small_cell():
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_compilation_cache_max_size",
        "jax_persistent_cache_min_compile_time_secs")}
    bench = harness.load_benchmark()
    cell, config, mix = harness.load_cell(bench, CELL)
    yield small.small(config, mix)
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_sound_run_is_correct(small_cell):
    res = small.run(CELL)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_is_not_correct(small_cell, fault):
    res = small.run(CELL, faults.FAULTS[fault])
    assert not res["correct"], res["checks"]


def test_control_is_not_correct(small_cell):
    config, mix = small_cell
    numbers = harness.control_numbers(config, small.SEED,
                                      gen.gen_pool(mix, small.SEED))
    ok, checks = check.judge(numbers, config["correct_limits"])
    assert not ok, checks
    assert checks["dense_rel_err"]["value"] > checks["dense_rel_err"]["limit"]
    assert np.isfinite(checks["loss_gap"]["value"])


def _compiled(interpret=False, apply="grouped", fit="fused"):
    def report(path):
        return lambda: {"x": {"path": path, "reason": "r",
                              "reason_kind": ""}}
    return types.SimpleNamespace(interpret=interpret,
                                 lowering_report=report(apply),
                                 fit_lowering_report=report(fit))


@pytest.mark.parametrize("case", ["interpret", "apply", "fit"])
def test_other_lowering_than_stated_is_refused(case):
    expect = {"apply": "grouped", "fit": "fused"}
    harness.require_lowering(_compiled(), expect, "tpu")
    broken = {"interpret": _compiled(interpret=True),
              "apply": _compiled(apply="staged"),
              "fit": _compiled(fit="staged")}[case]
    with pytest.raises(RuntimeError):
        harness.require_lowering(broken, expect, "tpu")
