"""Fig 14 analogue: trainer utilization — blocking CPU-style feed vs the
staged prefetching executor (same ETL, same trainer) — plus the Fig-8-style
per-stage occupancy breakdown from the executor's stage stats.

Ingest runs through the session facade (``EtlJob`` over a ``Source``); the
blocking baseline iterates the same Source inline on the critical path.

Emits:
  fig14/blocking, fig14/overlapped           (jnp device ETL)
  fig14/cpu_fed_blocking, fig14/cpu_fed_overlapped  (numpy host ETL — the
      paper's headline regime: slow CPU ETL hidden behind the train step)
  fig8/<stage>                                per-stage breakdown
  fig8/consumer_wait                          time the trainer starved
  fig14/utilization_gain                      overlapped - blocking (pp)

``--steps N`` overrides the batch count (CI smoke: ``--steps 3`` exercises
the executor path end-to-end without asserting the utilization win, which
needs enough batches to amortize warmup).

``--sweep`` runs the Fig-8 sensitivity grid instead: credits x
stage-cost-ratio cells with pinned (sleep-based) stage costs, emitting
the time the trainer starved per cell —

  fig8_sweep/credits=C_ratio=R

The deterministic costs isolate the staging-depth effect: starvation
should fall with credits while ETL is the bottleneck (ratio > 1) and
vanish once ETL hides (ratio <= 1, credits >= 2).
``--sweep-credits`` / ``--sweep-ratios`` override the grid (the nightly CI
smoke runs a single cell).

``--autotune`` (with ``--sweep``) adds one controller-driven cell per
ratio: the same pinned-cost workload starts at credits=1 and lets the
self-tuning ``PipelineController`` pick the staging depth live —

  fig8_sweep/autotuned_ratio=R

the row reports the knobs the controller landed on, for eyeballing
against the exhaustively-swept cells.  ``--json [PATH]`` writes the
machine-readable sweep trajectory (default ``BENCH_10.json`` at the repo
root) with every record stamped with the git SHA and the resolved
interpret mode, so hardware and interpret baselines never get compared.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import time

import jax
import numpy as np

from benchmarks.common import emit, git_sha
from repro.configs.base import TrainConfig
from repro.core.pipeline import paper_pipeline
from repro.data.source import Source
from repro.models import dlrm
from repro.session import EtlJob
from repro.training.train_loop import TrainState, make_train_step

BATCH = 4096


def _make_step(cfg, tcfg):
    return jax.jit(make_train_step(lambda p, b: dlrm.loss_fn(p, b, cfg),
                                   tcfg), donate_argnums=0)


def _source(n_batches: int) -> Source:
    return Source.synth("I", rows=n_batches * BATCH, batch_size=BATCH, seed=2)


def _fresh_job(backend: str, n_batches: int) -> EtlJob:
    job = EtlJob(paper_pipeline("II", small_vocab=8192, batch_size=BATCH),
                 _source(n_batches), backend=backend,
                 fit_source=Source.synth("I", rows=8192, batch_size=8192))
    job.fit()
    return job


def _materialize(batch):
    return {k: np.asarray(v) for k, v in batch.items()}


def run_blocking(job, step, state, *, host_etl):
    """ETL inline on the critical path (the paper's CPU-GPU mode)."""
    t0 = time.perf_counter()
    train_s = 0.0
    for raw in job.apply_source():
        batch = job.apply(raw)
        if host_etl:
            batch = _materialize(batch)
        ts = time.perf_counter()
        state, m = step(state, batch)
        jax.block_until_ready(m["loss"])
        train_s += time.perf_counter() - ts
    total = time.perf_counter() - t0
    return train_s / total, total


def run_overlapped(job, step, state):
    """Staged prefetching executor: ETL stages overlap the train step."""
    t0 = time.perf_counter()
    train_s = 0.0
    with job.batches() as ex:
        for batch in ex:
            ts = time.perf_counter()
            state, m = step(state, batch)
            jax.block_until_ready(m["loss"])
            train_s += time.perf_counter() - ts
    total = time.perf_counter() - t0
    return train_s / total, total, job.stats()


def _sweep_cell(job, steps, train_s):
    """Run one pinned-cost cell; return (wall_s, stats)."""
    t0 = time.perf_counter()
    with job.batches() as ex:
        for _ in ex:
            time.sleep(train_s)
    return time.perf_counter() - t0, job.stats()


def run_sweep(credits_list, ratios, steps, *, autotune=False):
    """Credits x stage-cost-ratio sensitivity sweep (Fig-8, ROADMAP item).

    Stage costs are pinned sleeps (deterministic, hardware-independent):
    the transform stage costs ``ratio`` x the train step.  Each cell runs
    the real staged executor through the ``EtlJob`` facade and reports the
    time the trainer starved (``consumer_wait_s``).

    With ``autotune``, one extra cell per ratio starts at credits=1 and
    lets the PipelineController choose the staging depth from measured
    windows — the controller-chosen row of the grid.  Returns the
    machine-readable record list (one dict per cell).
    """
    train_s = 0.004
    records = []

    def make_job(credits, ratio, **kw):
        etl_s = train_s * ratio

        def transform(raw, _etl_s=etl_s):
            time.sleep(_etl_s)
            return raw

        src = Source.stream(lambda: iter([{"i": np.arange(8)}] * steps))
        return EtlJob(transform, src, credits=credits, **kw)

    for credits in credits_list:
        for ratio in ratios:
            job = make_job(credits, ratio, name=f"sweep-c{credits}-r{ratio}")
            wall, stats = _sweep_cell(job, steps, train_s)
            emit(f"fig8_sweep/credits={credits}_ratio={ratio:g}", wall,
                 f"starved={stats.consumer_wait_s:.3f}s")
            records.append(dict(mode="sweep", credits=credits, ratio=ratio,
                                steps=steps, wall_s=wall,
                                starved_s=stats.consumer_wait_s))
    if autotune:
        for ratio in ratios:
            job = make_job(1, ratio, autotune=True,
                           max_credits=max(credits_list),
                           name=f"sweep-autotuned-r{ratio}")
            wall, stats = _sweep_cell(job, steps, train_s)
            ctl = stats.controller
            chosen = ctl.knob_values() if ctl is not None else {}
            decisions = ctl.decision_counts() if ctl is not None else {}
            emit(f"fig8_sweep/autotuned_ratio={ratio:g}", wall,
                 f"starved={stats.consumer_wait_s:.3f}s|chosen="
                 + ",".join(f"{k}={v}" for k, v in sorted(chosen.items())))
            records.append(dict(mode="autotuned", ratio=ratio, steps=steps,
                                wall_s=wall,
                                starved_s=stats.consumer_wait_s,
                                chosen=chosen, decisions=decisions))
    return records


def _csv(kind):
    return lambda s: [kind(v) for v in s.split(",") if v]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=12,
                    help="batches per run (smoke: 3)")
    ap.add_argument("--sweep", action="store_true",
                    help="run the credits x stage-cost-ratio sweep instead")
    ap.add_argument("--sweep-credits", type=_csv(int), default=[1, 2, 4],
                    help="comma-separated credit depths for --sweep")
    ap.add_argument("--sweep-ratios", type=_csv(float),
                    default=[0.5, 1.0, 2.0],
                    help="comma-separated ETL/train cost ratios for --sweep")
    ap.add_argument("--autotune", action="store_true",
                    help="with --sweep: add a controller-chosen cell per "
                         "ratio (self-tuning PipelineController)")
    ap.add_argument("--json", nargs="?", const="", default=None,
                    metavar="PATH",
                    help="with --sweep: write the machine-readable records "
                         "(default: BENCH_10.json at the repo root)")
    args = ap.parse_args(argv)
    n = args.steps
    if args.sweep:
        records = run_sweep(args.sweep_credits, args.sweep_ratios, n,
                            autotune=args.autotune)
        if args.json is not None:
            from repro.kernels.ops import default_interpret
            sha, interpret = git_sha(), default_interpret()
            for r in records:
                r["git_sha"] = sha
                r["interpret"] = interpret
            path = pathlib.Path(args.json) if args.json else (
                pathlib.Path(__file__).resolve().parent.parent
                / "BENCH_10.json")
            path.write_text(json.dumps({
                "bench": "overlap_sweep",
                "git_sha": sha,
                "interpret": interpret,
                "records": records,
            }, indent=2) + "\n")
            print(f"wrote {path}", flush=True)
        return

    cfg = dlrm.DLRMConfig(vocab_size=8193, d_emb=32, bot_mlp=(128, 64, 32),
                          top_mlp=(128, 64, 1))
    tcfg = TrainConfig(lr=1e-3)
    step = _make_step(cfg, tcfg)

    def fresh_state():
        return TrainState.create(dlrm.init(jax.random.key(0), cfg), tcfg)

    # device (jnp) ETL: async dispatch already hides most of it
    util_block, total_block = run_blocking(_fresh_job("jnp", n), step,
                                           fresh_state(), host_etl=True)
    emit("fig14/blocking", total_block, f"util={util_block:.2%}")
    util_ov, total_ov, _ = run_overlapped(_fresh_job("jnp", n), step,
                                          fresh_state())
    emit("fig14/overlapped", total_ov,
         f"util={util_ov:.2%}|speedup={total_block / total_ov:.2f}x")

    # the paper's Fig-1/14 regime: slow host (numpy) ETL on the critical
    # path vs the same producer overlapped — the utilization gap is the
    # headline effect
    cpu_block, cpu_block_total = run_blocking(_fresh_job("numpy", n), step,
                                              fresh_state(), host_etl=False)
    emit("fig14/cpu_fed_blocking", cpu_block_total,
         f"util={cpu_block:.2%}")
    cpu_ov, cpu_ov_total, stats = run_overlapped(_fresh_job("numpy", n), step,
                                                 fresh_state())
    emit("fig14/cpu_fed_overlapped", cpu_ov_total,
         f"util={cpu_ov:.2%}|speedup={cpu_block_total / cpu_ov_total:.2f}x")

    # Fig-8-style per-stage breakdown of the overlapped CPU-fed run
    for name, s in stats.stage_breakdown().items():
        emit(f"fig8/{name}", s["busy_s"],
             f"items={s['items']}|wait_in={s['wait_in_s']:.3f}s"
             f"|wait_out={s['wait_out_s']:.3f}s|occ={s['occupancy']:.1%}")
    emit("fig8/consumer_wait", stats.consumer_wait_s,
         f"trainer_starved={stats.consumer_wait_s:.3f}s")

    gain_pp = (cpu_ov - cpu_block) * 100
    emit("fig14/utilization_gain", cpu_ov_total,
         f"overlap_gain={gain_pp:.1f}pp")
    if n >= 8:  # smoke runs are too short to assert the win
        assert cpu_ov > cpu_block, (
            f"overlap must beat blocking: {cpu_ov:.2%} vs {cpu_block:.2%}")


if __name__ == "__main__":
    main()
