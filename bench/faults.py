"""Faults planted in the system under test, for the check's own tests and
for reading each fault's numbers on the chip.

Each is a context manager that patches the program where the harness
finds it (module attributes read at call time), so the run drives the
timed path with the fault underneath:

- ``unchanged_state``: the train step returns the state it was given;
- ``half_batch``: the loss is the mean over the first half of the rows;
- ``altered_answer``: the ETL's packed batch has one sparse id changed
  where the apply program produces it.

A one-chip cell has no exchange between chips to leave out.
"""

from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def unchanged_state():
    from repro.training import train_loop as tl
    make = tl.make_train_step

    def make_broken(loss_fn, tcfg, grad_specs=None):
        step = make(loss_fn, tcfg, grad_specs)

        def train_step(state, batch):
            _, metrics = step(state, batch)
            return state, metrics
        return train_step
    return _patched(tl, "make_train_step", make_broken)


def half_batch():
    from repro.models import dlrm
    loss = dlrm.loss_fn

    def loss_half(params, batch, cfg):
        n = batch["label"].shape[0] // 2
        return loss(params, {k: v[:n] for k, v in batch.items()}, cfg)
    return _patched(dlrm, "loss_fn", loss_half)


def altered_answer():
    from repro.core.compiler import CompiledPipeline
    apply = CompiledPipeline.apply_versioned

    def apply_altered(self, raw_batch):
        out, version = apply(self, raw_batch)
        out = dict(out)
        out["sparse"] = out["sparse"].at[0, 0].add(1)
        return out, version
    return _patched(CompiledPipeline, "apply_versioned", apply_altered)


FAULTS = {"unchanged_state": unchanged_state, "half_batch": half_batch,
          "altered_answer": altered_answer}
