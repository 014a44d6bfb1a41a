"""End-to-end driver (paper Fig 3): streaming ETL -> P2P handoff -> DLRM.

    PYTHONPATH=src python examples/train_dlrm_e2e.py [--steps 300]

Trains a ~100M-parameter DLRM for a few hundred steps on a continuously
generated Criteo-like event stream.  Ingest is declarative: a ``Source``
names the stream and an ``EtlJob`` owns compile -> fit -> the staged
prefetching executor (Pipeline II runs in the producer threads,
double-buffered against the trainer with credit backpressure); the script
reports rows/s, the time the trainer starved for batches and the
per-stage breakdown (paper Fig 14 / §4.4).
"""

import argparse
import time

import jax

from repro.configs.base import TrainConfig
from repro.core.pipeline import paper_pipeline
from repro.data.source import Source
from repro.models import dlrm
from repro.session import EtlJob
from repro.training.train_loop import (LoopConfig, TrainState, make_train_step,
                                       train_loop)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=2048)
    ap.add_argument("--vocab", type=int, default=65536)
    ap.add_argument("--ckpt-dir", default="")
    args = ap.parse_args()

    # ~100M params: 26 tables x 64k x 64
    cfg = dlrm.DLRMConfig(vocab_size=args.vocab + 1, d_emb=64,
                          bot_mlp=(512, 256, 64),
                          top_mlp=(512, 256, 128, 1))
    print(f"[e2e] DLRM params: {cfg.param_count():,}")

    job = EtlJob(
        paper_pipeline("II", small_vocab=args.vocab, batch_size=args.batch),
        Source.synth("I", rows=args.steps * args.batch,
                     batch_size=args.batch, seed=11),
        backend="jnp",
        fit_source=Source.synth("I", rows=50_000, batch_size=10_000))
    t0 = time.perf_counter()
    job.fit()
    print(f"[e2e] vocab fit in {time.perf_counter()-t0:.2f}s; "
          f"n_unique={max(job.state.n_unique.values())}")

    tcfg = TrainConfig(lr=1e-3)
    state = TrainState.create(dlrm.init(jax.random.key(0), cfg), tcfg)
    # donate the packed batch too: it arrives pre-placed from the executor
    # and is consumed exactly once, so XLA may reuse its HBM in-step
    # (the CPU backend cannot alias donated inputs, so gate on device)
    donate = (0, 1) if jax.default_backend() != "cpu" else (0,)
    step = jax.jit(make_train_step(lambda p, b: dlrm.loss_fn(p, b, cfg),
                                   tcfg), donate_argnums=donate)

    t0 = time.perf_counter()
    with job.batches() as ex:
        state = train_loop(state, step, ex,
                           LoopConfig(total_steps=args.steps,
                                      ckpt_dir=args.ckpt_dir,
                                      ckpt_every=100 if args.ckpt_dir else 0,
                                      log_every=50))
    wall = time.perf_counter() - t0
    s = job.stats()
    rows = args.steps * args.batch
    print(f"[e2e] {args.steps} steps / {rows:,} rows in {wall:.1f}s "
          f"({rows/wall:,.0f} rows/s)")
    print(f"[e2e] trainer starved {s.consumer_wait_s:.2f}s; "
          f"ETL blocked on credits {s.producer_wait_s:.2f}s")
    for name, st in s.stage_breakdown().items():
        print(f"[e2e]   stage {name:9s} items={st['items']:<5d} "
              f"busy={st['busy_s']:.2f}s wait_in={st['wait_in_s']:.2f}s "
              f"wait_out={st['wait_out_s']:.2f}s occ={st['occupancy']:.1%} "
              f"bytes={st['bytes']:,}")


if __name__ == "__main__":
    main()
