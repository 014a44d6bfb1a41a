"""Training launcher: ETL-fed, checkpointed, fault-tolerant.

Local smoke run (CPU)::

    PYTHONPATH=src python -m repro.launch.train --arch llama3_2_3b \
        --reduced --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

Production posture: same entry point with --mesh pod runs under the
16x16 production mesh (requires a real pod or the dry-run device flags);
every run is restartable — on startup the launcher restores the newest
committed checkpoint if one exists (elastic: the mesh geometry may differ
from the one that wrote it).
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs.base import ShapeCfg
from repro.configs.registry import get_config, get_reduced
from repro.core.pipeline import lm_token_pipeline
from repro.data.source import Source
from repro.distributed import sharding as shd
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.launch.presets import train_preset
from repro.models.api import build_model, input_specs
from repro.session import EtlJob
from repro.training import checkpoint as ckpt_lib
from repro.training.fault import run_with_restarts
from repro.training.train_loop import (LoopConfig, TrainState, jit_train_step,
                                       make_train_step, train_loop)


def make_job(cfg, batch, seq, steps, *, backend="jnp", mesh=None,
             metrics_file="", embed_cache=None, autotune=None) -> EtlJob:
    """Declarative ingest session: raw event logs -> token batches.

    The ``Source`` names the stream; ``EtlJob`` owns compile + executor
    lifecycle.  With a mesh, the executor's place stage double-buffers
    ``device_put`` with the trainer's batch ``NamedSharding``, so delivered
    batches are already laid out for ``train_step``'s ``in_shardings``.
    ``embed_cache`` (an ``EmbedCacheConfig``) adds the lookahead embedding
    prefetch stage — recommender pipelines whose batches carry a sparse
    index matrix; LM pipelines have no such key and must leave it unset.
    """
    pipe = lm_token_pipeline(seq, cfg.vocab_size, batch_size=batch)
    src = Source.lm_events(seq, rows=batch * (steps + 4), batch_size=batch)
    return EtlJob(pipe, src, backend=backend, mesh=mesh, credits=2,
                  metrics_file=metrics_file, embed_cache=embed_cache,
                  autotune=autotune, metrics_labels={"arch": cfg.name})


def embed_cache_config(args):
    """CLI knobs -> EmbedCacheConfig (None when the cache is off)."""
    if args.embed_cache_rows <= 0:
        return None
    from repro.etl_runtime.lookahead import EmbedCacheConfig
    tables = (tuple(int(t) for t in args.embed_cache_tables.split(","))
              if args.embed_cache_tables else None)
    return EmbedCacheConfig(rows=args.embed_cache_rows,
                            window=args.embed_cache_window,
                            tables=tables, key=args.embed_cache_key)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", choices=["host", "pod", "multipod"],
                    default="host")
    ap.add_argument("--etl-backend", default="jnp",
                    choices=["numpy", "jnp", "pallas"])
    ap.add_argument("--watchdog-s", type=float, default=0.0)
    ap.add_argument("--max-restarts", type=int, default=2)
    ap.add_argument("--metrics-file", default="",
                    help="write executor StageStats as Prometheus text here")
    ap.add_argument("--embed-cache-rows", type=int, default=0,
                    help="device-resident embedding-cache rows per table "
                         "(0 = lookahead prefetch off)")
    ap.add_argument("--embed-cache-window", type=int, default=4,
                    help="lookahead window W (batches) for hot-set planning")
    ap.add_argument("--embed-cache-tables", default="",
                    help="comma-separated feature columns to cache "
                         "(default: all columns of the index matrix)")
    ap.add_argument("--embed-cache-key", default="sparse",
                    help="payload key holding the [batch, tables] indices")
    ap.add_argument("--autotune", action="store_true",
                    help="run the self-tuning PipelineController over the "
                         "executor knobs (credits, prefetch depth, "
                         "lookahead window; row tile/fuse on pallas)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    tcfg = train_preset(args.arch)
    model = build_model(cfg)
    if args.mesh == "host":
        mesh = make_host_mesh()
    else:
        mesh = make_production_mesh(multi_pod=args.mesh == "multipod")
    shd.set_active_mesh(mesh)

    def make_run():
        def run():
            shape = ShapeCfg("cli", args.seq, args.batch, "train")
            state_shapes = jax.eval_shape(
                lambda: TrainState.create(model.init(jax.random.key(0)), tcfg))
            batch_shapes = input_specs(cfg, shape)
            # batches come from the streaming executor and are consumed
            # exactly once, already placed in the step's in_shardings layout
            # — donate them so the handoff is zero-copy end to end
            step_fn, state_spec = jit_train_step(
                make_train_step(model.loss, tcfg), mesh, state_shapes,
                batch_shapes, fsdp=tcfg.fsdp,
                n_experts=cfg.moe.n_experts if cfg.moe else 0,
                donate_batch=True)

            def make_state():
                return TrainState.create(model.init(jax.random.key(0)), tcfg)

            from jax.sharding import NamedSharding, PartitionSpec
            shardings = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), state_spec,
                is_leaf=lambda x: isinstance(x, PartitionSpec))
            latest = (ckpt_lib.latest_step(args.ckpt_dir)
                      if args.ckpt_dir else None)
            if latest is not None:
                print(f"[train] resuming from step {latest}")
                zeros = jax.tree_util.tree_map(
                    lambda s: np.zeros(s.shape, s.dtype), state_shapes)
                state = ckpt_lib.restore(args.ckpt_dir, zeros,
                                         shardings=shardings)
            else:
                state = make_state()

            job = make_job(cfg, args.batch, args.seq, args.steps,
                           backend=args.etl_backend, mesh=mesh,
                           metrics_file=args.metrics_file,
                           embed_cache=embed_cache_config(args),
                           autotune=args.autotune or None)
            loop_cfg = LoopConfig(total_steps=args.steps,
                                  ckpt_dir=args.ckpt_dir,
                                  ckpt_every=args.ckpt_every,
                                  log_every=10,
                                  watchdog_s=args.watchdog_s)
            t0 = time.perf_counter()
            with mesh, job.batches() as batches:
                final = train_loop(state, step_fn, batches, loop_cfg)
            dt = time.perf_counter() - t0
            toks = args.steps * args.batch * args.seq
            stats = job.stats()
            print(f"[train] done: {args.steps} steps, "
                  f"{toks/dt:,.0f} tok/s, etl_producer_wait="
                  f"{stats.producer_wait_s:.2f}s trainer_wait="
                  f"{stats.consumer_wait_s:.2f}s "
                  f"util={stats.trainer_utilization(dt - stats.consumer_wait_s):.2%}")
            for name, s in stats.stage_breakdown().items():
                print(f"[train]   stage {name:9s} items={s['items']:<5d} "
                      f"busy={s['busy_s']:.2f}s wait_in={s['wait_in_s']:.2f}s "
                      f"wait_out={s['wait_out_s']:.2f}s "
                      f"occ={s['occupancy']:.1%}")
            if args.metrics_file:
                print(f"[train] metrics written to {args.metrics_file}")
            return final

        return run

    run_with_restarts(make_run, max_restarts=args.max_restarts)


if __name__ == "__main__":
    main()