"""Train-step construction + the checkpointed, fault-tolerant driver loop."""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import TrainConfig
from repro.distributed import sharding as shd
from repro.training import checkpoint as ckpt_lib
from repro.training import fault as fault_lib
from repro.training.grad import microbatched_value_and_grad
from repro.training.optimizer import opt_init, opt_update


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: Any
    opt: Any
    step: jax.Array

    @staticmethod
    def create(params, tcfg: TrainConfig) -> "TrainState":
        return TrainState(params=params, opt=opt_init(params, tcfg),
                          step=jnp.zeros((), jnp.int32))


def make_train_step(loss_fn: Callable, tcfg: TrainConfig,
                    grad_specs=None) -> Callable:
    """loss_fn(params, batch) -> scalar. Returns step(state, batch)."""
    n_micro = max(tcfg.microbatch, 1)
    vg = microbatched_value_and_grad(loss_fn, n_micro,
                                     accum_dtype=tcfg.accum_dtype,
                                     grad_specs=grad_specs)

    def train_step(state: TrainState, batch) -> tuple[TrainState, dict]:
        with jax.named_scope("train_step"):
            loss, grads = vg(state.params, batch)
            new_p, new_opt, gnorm = opt_update(grads, state.opt, state.params,
                                               state.step, tcfg)
        new_state = TrainState(params=new_p, opt=new_opt,
                               step=state.step + 1)
        return new_state, {"loss": loss.astype(jnp.float32),
                           "grad_norm": gnorm}

    return train_step


def jit_train_step(train_step, mesh, state_shapes, batch_shapes, *,
                   fsdp: bool = False, n_experts: int = 0,
                   donate_batch: bool = False):
    """pjit the step with explicit in/out shardings and state donation.

    ``donate_batch=True`` additionally donates the batch argument, which
    the streaming executor's place stage already delivers in the exact
    ``in_shardings`` layout.  XLA can only reuse a donated buffer for an
    output of the same shape; a DLRM step has none, and XLA:TPU warns that
    the donated batch is unusable.  Only enable it when every batch is
    consumed exactly once (always true for executor-fed loops); a donated
    batch is invalid after the step.  The CPU backend cannot alias donated
    inputs, so the request is ignored there (no warning spam on smoke
    runs).

    NOTE: for grad-accumulation sharding, build the step via
    ``make_train_step(loss, tcfg, grad_specs=param_specs(...))``.
    """
    donate_batch = donate_batch and jax.default_backend() != "cpu"
    pspec = shd.param_specs(state_shapes.params, mesh, fsdp=fsdp,
                            n_experts=n_experts)
    # optimizer moments run through the same rule engine: AdamW m/v paths end
    # with the param name so the same rule fires; Adafactor's factored vr/vc
    # take the default (FSDP-sharded when enabled — ZeRO covers opt state too)
    opt_spec = shd.param_specs(state_shapes.opt, mesh, fsdp=fsdp,
                               n_experts=n_experts)
    state_spec = TrainState(params=pspec, opt=opt_spec, step=P())
    batch_spec = shd.batch_specs(batch_shapes, mesh)
    to_sh = lambda tree: jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P))
    return jax.jit(train_step,
                   in_shardings=(to_sh(state_spec), to_sh(batch_spec)),
                   out_shardings=(to_sh(state_spec), None),
                   donate_argnums=(0, 1) if donate_batch else (0,)), state_spec


# ---------------------------------------------------------------------------
# training loop: checkpoint/restart + watchdog + step spans
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class LoopConfig:
    total_steps: int
    ckpt_dir: str = ""
    ckpt_every: int = 0
    log_every: int = 50
    watchdog_s: float = 0.0
    keep_ckpts: int = 3


def train_loop(state: TrainState, step_fn, batches, loop_cfg: LoopConfig,
               *, async_ckpt: bool = True, on_metrics=None,
               embed_cache=None, embed_tables=None) -> TrainState:
    """Run to total_steps with periodic async checkpoints + watchdog.

    ``batches`` may be a plain iterable or a staged ``StreamingExecutor``;
    an executor is stopped on exit (so breaking at ``total_steps`` tears the
    prefetch stages down promptly) and its stats surface in the metrics.

    ``embed_cache`` threads a ``lookahead.EmbedCache`` alongside the train
    state: before each step the batch's lookahead plan is applied against
    the CURRENT embedding tables (``embed_tables(state.params)``, default
    ``params["tables"]``) so the cached forward reads fresh rows.  Plans
    must be applied in delivery order — the loop is that order.
    """
    ckpt = ckpt_lib.AsyncCheckpointer() if async_ckpt else None
    wd = fault_lib.Watchdog(loop_cfg.watchdog_s) if loop_cfg.watchdog_s else None
    etl_stats = getattr(batches, "stats", None)
    if embed_cache is not None and embed_tables is None:
        embed_tables = lambda params: params["tables"]
    try:
        for batch in batches:
            step_no = int(state.step)
            if step_no >= loop_cfg.total_steps:
                break
            if embed_cache is not None:
                batch = embed_cache.advance(embed_tables(state.params), batch)
            if wd:
                wd.arm()
            with jax.profiler.StepTraceAnnotation("train.step",
                                                  step_num=step_no):
                state, metrics = step_fn(state, batch)
                jax.block_until_ready(metrics["loss"])
            if wd:
                wd.check()
                wd.disarm()
            step_no = int(state.step)
            if loop_cfg.log_every and step_no % loop_cfg.log_every == 0:
                m = {k: float(v) for k, v in metrics.items()}
                m["step"] = step_no
                if etl_stats is not None:
                    m["etl_starved_s"] = etl_stats.consumer_wait_s
                    cache = getattr(etl_stats, "cache", None)
                    if cache is not None:
                        m["emb_cache_hit_rate"] = cache.hit_rate()
                if on_metrics:
                    on_metrics(m)
                else:
                    print(f"[train] step={step_no} "
                          + " ".join(f"{k}={v:.5g}" for k, v in m.items()
                                     if k != "step"), flush=True)
            if (loop_cfg.ckpt_every and loop_cfg.ckpt_dir
                    and step_no % loop_cfg.ckpt_every == 0):
                if ckpt:
                    ckpt.save_async(state, loop_cfg.ckpt_dir, step_no)
                else:
                    ckpt_lib.save(state, loop_cfg.ckpt_dir, step_no)
                ckpt_lib.prune(loop_cfg.ckpt_dir, loop_cfg.keep_ckpts)
    finally:
        stop = getattr(batches, "stop", None)
        if callable(stop):
            stop()
        if ckpt:
            ckpt.wait()
        if wd:
            wd.close()
    return state


def resume_or_init(make_state: Callable[[], TrainState], ckpt_dir: str,
                   shardings=None) -> TrainState:
    """Restore the latest committed checkpoint, else build fresh state."""
    template = jax.eval_shape(make_state)
    step = ckpt_lib.latest_step(ckpt_dir) if ckpt_dir else None
    if step is None:
        return make_state()
    zeros = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), template)
    return ckpt_lib.restore(ckpt_dir, zeros, step=step, shardings=shardings)