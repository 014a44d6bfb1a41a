"""Chip smoke test: ETL-fed DLRM training on a TPU through the normal entry points.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the data-parallel path of a 4-chip host

One chip: the paper's Pipeline II (vocabulary 131,072) on the Dataset-I shape
(13 dense, 26 hex sparse columns) runs through ``EtlJob`` with
``backend="pallas"``: ``fit()`` over 8 batches of 65,536 rows, then
``batches()`` feeds 20 AdamW steps of DLRM at its default widths (tables cut
to 131,073 rows) through ``train_loop``.  The fitted vocabulary and the first
delivered batch are checked against the numpy oracle, and every loss must be
finite.

``--chips 4``: the same fit and steps run data-parallel on a 4-chip mesh
(``make_host_mesh``, ``EtlJob(mesh=...)``, ``jit_train_step``), then again on
one chip with the same batches in the same process; the per-step losses must
agree within ``LOSS_RTOL``, every batch must span the 4 chips, and the
parameters must not all sit on the first chip.

Times and rates printed are smoke numbers from one cold run, not benchmark
results.  The last line of a passing run is one JSON object naming the
device; a failed check or an error exits non-zero without it.
"""

from __future__ import annotations

import argparse
import itertools
import json
import math
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

BATCH = 65536       # paper_pipeline's batch; the step fits one v5e at this size
VOCAB = 131072      # Pipeline II vocabulary; DLRM tables get one more (OOV) row
FIT_BATCHES = 8
STEPS = 20
SEED = 11
# float columns vs the numpy oracle: log1p_f32 and numpy's float32 log1p
# are each within 2 ulp of the exact value
DENSE_RTOL, DENSE_ATOL = 1e-6, 0.0
LOSS_RTOL = 1e-3    # 4-chip vs 1-chip loss: all-reduce order, 20 AdamW steps


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SystemExit(f"[smoke] FAIL: {msg}")


def require_tpu(chips: int):
    import jax
    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"JAX found no TPU (platform {devs[0].platform!r})")
    check(len(devs) >= chips, f"{chips} chips asked for, {len(devs)} found")
    log(f"device_kind={devs[0].device_kind} devices={len(devs)}")
    return devs


def require_compiled(job) -> None:
    interpret = job.compiled.interpret
    log(f"pallas interpret={interpret}")
    check(not interpret, "Pallas kernels resolved to interpret mode")


def build(mesh=None):
    """(pipeline template, EtlJob, fit source) for Pipeline II over the
    synthetic Dataset-I stream; the fit reads its first FIT_BATCHES."""
    from repro.core.pipeline import paper_pipeline
    from repro.data.source import Source
    from repro.session import EtlJob
    pipe = paper_pipeline("II", small_vocab=VOCAB, batch_size=BATCH)
    stream = lambda n: Source.synth("I", rows=n * BATCH, batch_size=BATCH,
                                    seed=SEED)
    fit_src = stream(FIT_BATCHES)
    job = EtlJob(pipe, stream(STEPS), backend="pallas", fit_source=fit_src,
                 mesh=mesh)
    return pipe, job, fit_src


def fit(job) -> None:
    t0 = time.perf_counter()
    job.fit()
    log(f"fit: {FIT_BATCHES} x {BATCH} rows in "
        f"{time.perf_counter() - t0:.3f}s (compile included)")


def dlrm_setup():
    import jax
    from repro.configs.base import TrainConfig
    from repro.models import dlrm
    from repro.training.train_loop import TrainState, make_train_step
    cfg = dlrm.DLRMConfig(vocab_size=VOCAB + 1)
    tcfg = TrainConfig(lr=1e-3)
    log(f"DLRM params={cfg.param_count():,} (d_emb={cfg.d_emb}, "
        f"bot={cfg.bot_mlp}, top={cfg.top_mlp}, tables=26 x {VOCAB + 1})")
    make_state = lambda: TrainState.create(
        dlrm.init(jax.random.key(SEED), cfg), tcfg)
    step = make_train_step(lambda p, b: dlrm.loss_fn(p, b, cfg), tcfg)
    return make_state, step


def train(state, step_fn, batches, tag: str):
    """``train_loop`` over ``batches``; returns (state, per-step losses,
    wall seconds)."""
    import jax
    from repro.training.train_loop import LoopConfig, train_loop
    losses = []

    def on_metrics(m):
        losses.append(m["loss"])
        log(f"{tag} step={m['step']} loss={m['loss']!r}")

    t0 = time.perf_counter()
    state = train_loop(state, step_fn, batches,
                       LoopConfig(total_steps=STEPS, log_every=1),
                       on_metrics=on_metrics)
    jax.block_until_ready(state)
    wall = time.perf_counter() - t0
    check(len(losses) == STEPS, f"{tag}: {len(losses)} of {STEPS} steps ran")
    check(all(math.isfinite(v) for v in losses), f"{tag}: non-finite loss")
    return state, losses, wall


def compile_and_train(state, step_fn, batches, tag: str):
    """Compile ``step_fn`` for the first batch, timed on its own, then
    ``train`` over every batch, so the rate leaves compilation out."""
    batches = iter(batches)
    first = next(batches)
    t0 = time.perf_counter()
    compiled = step_fn.lower(state, first).compile()
    log(f"{tag} train step compile: {time.perf_counter() - t0:.3f}s")
    return train(state, compiled, itertools.chain([first], batches), tag)


def check_against_oracle(pipe, job, fit_src, raw, packed) -> None:
    """The fitted vocabulary and one packed batch against the numpy
    backend fitted on the same batches: integer columns exactly, float
    columns within DENSE_RTOL / DENSE_ATOL."""
    oracle = pipe.compile(backend="numpy")
    oracle.fit(iter(fit_src))
    for vid, table in oracle.state.tables.items():
        np.testing.assert_array_equal(np.asarray(job.state.tables[vid]),
                                      table, err_msg=f"vocab {vid}")
    want = oracle(raw)
    for k, w in want.items():
        got = np.asarray(packed[k])
        check(got.shape == w.shape and got.dtype == w.dtype,
              f"{k}: {got.shape} {got.dtype} != oracle {w.shape} {w.dtype}")
        if np.issubdtype(w.dtype, np.integer):
            np.testing.assert_array_equal(got, w, err_msg=k)
        else:
            np.testing.assert_allclose(got, w, rtol=DENSE_RTOL,
                                       atol=DENSE_ATOL, err_msg=k)
    log(f"first packed batch == numpy oracle ({', '.join(sorted(want))}); "
        f"vocab tables equal (n_unique={dict(job.state.n_unique)})")


def one_chip(devs) -> None:
    import jax
    pipe, job, fit_src = build()
    cp = job.compiled
    require_compiled(job)
    for name, rep in cp.lowering_report().items():
        log(f"apply {name}: path={rep['path']} "
            f"reason_kind={rep['reason_kind']!r}")
    for vid, rep in cp.fit_lowering_report().items():
        log(f"fit {vid}: path={rep['path']} "
            f"reason_kind={rep['reason_kind']!r}")
    raw = next(iter(job.apply_source()))
    log(f"traced_pallas_call_count apply={cp.traced_pallas_call_count(raw)} "
        f"fit={cp.traced_pallas_call_count(raw, phase='fit')}")
    fit(job)

    make_state, step = dlrm_setup()
    state = make_state()
    step = jax.jit(step, donate_argnums=(0, 1))
    with job.batches() as ex:
        it = iter(ex)
        first = next(it)
        check_against_oracle(pipe, job, fit_src, raw, first)
        state, _, wall = compile_and_train(
            state, step, itertools.chain([first], it), "1chip")
    peak = devs[0].memory_stats()["peak_bytes_in_use"]
    log(f"peak_bytes_in_use={peak} ({peak / 2**30:.2f} GiB)")
    log(f"smoke rate (one cold run, not a benchmark): "
        f"{STEPS * BATCH / wall:,.0f} rows/s over {STEPS} steps, "
        f"{wall:.3f}s")


def four_chips(devs) -> None:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.distributed import sharding as shd
    from repro.launch.mesh import make_host_mesh
    from repro.training.train_loop import jit_train_step

    mesh = make_host_mesh()
    mesh_devs = set(mesh.devices.flat)
    log(f"mesh {dict(mesh.shape)} over {len(mesh_devs)} chips")
    check(len(mesh_devs) == 4, f"mesh spans {len(mesh_devs)} chips, not 4")
    _, job, _ = build(mesh=mesh)
    require_compiled(job)
    fit(job)

    make_state, step = dlrm_setup()
    raw = next(iter(job.apply_source()))
    batch_shapes = {k: jax.ShapeDtypeStruct(np.shape(v), np.asarray(v).dtype)
                    for k, v in job.apply(raw).items()}
    shd.set_active_mesh(mesh)
    step4, state_spec = jit_train_step(
        step, mesh, jax.eval_shape(make_state), batch_shapes,
        donate_batch=True)
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), state_spec,
        is_leaf=lambda x: isinstance(x, PartitionSpec))
    state = jax.jit(make_state, out_shardings=shardings)()

    kept = []  # host copies of every delivered batch, for the 1-chip replay

    def placed_batches(ex):
        for b in ex:
            for k, v in b.items():
                shards = v.addressable_shards
                check(v.sharding.device_set == mesh_devs
                      and len(shards) == 4
                      and shards[0].data.shape[0] == BATCH // 4,
                      f"batch[{k!r}] is not row-sharded over the 4 chips")
            kept.append({k: np.asarray(v) for k, v in b.items()})
            yield b

    with job.batches() as ex:
        state, losses4, wall4 = compile_and_train(
            state, step4, placed_batches(ex), "4chip")
    log(f"every batch row-sharded over {len(mesh_devs)} chips "
        f"({BATCH // 4} rows each)")
    param_devs = {d for leaf in jax.tree_util.tree_leaves(state.params)
                  for d in leaf.sharding.device_set}
    check(param_devs != {devs[0]}, "parameters all sit on the first chip")
    log(f"parameters span {len(param_devs)} chips")
    del state
    shd.set_active_mesh(None)

    step1 = jax.jit(step, donate_argnums=(0, 1))
    replay = ({k: jax.device_put(v, devs[0]) for k, v in b.items()}
              for b in kept)
    state1, losses1, wall1 = compile_and_train(make_state(), step1, replay,
                                               "1chip")
    del state1
    np.testing.assert_allclose(losses4, losses1, rtol=LOSS_RTOL)
    worst = max(abs(a - b) / abs(b) for a, b in zip(losses4, losses1))
    log(f"4-chip losses == 1-chip losses on the same batches: worst "
        f"relative difference {worst!r} (rtol {LOSS_RTOL})")
    log(f"smoke rates (one cold run, not a benchmark): 4 chips "
        f"{STEPS * BATCH / wall4:,.0f} rows/s, 1 chip replay "
        f"{STEPS * BATCH / wall1:,.0f} rows/s")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the data-parallel path of a 4-chip host")
    args = ap.parse_args(argv)
    devs = require_tpu(args.chips)
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    if args.chips == 4:
        four_chips(devs)
    else:
        one_chip(devs)
    import jax
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
