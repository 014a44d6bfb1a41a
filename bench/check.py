"""The comparison that decides a run's ``correct``.

Numbers compared, each against a limit from the configuration file's
``correct_limits`` (set from readings of sound runs and of the control, as
``PERF.md`` records):

- ``vocab_mismatch``: table entries of the fitted vocabulary that differ
  from the reference's (exact, limit 0);
- ``sparse_mismatch``, ``label_mismatch``: entries of the checked packed
  batches that differ from the reference's (exact, limit 0);
- ``dense_rel_err``: the largest relative error of a packed dense value;
- ``loss_gap``: the largest relative gap of a checked step's loss;
- ``grad_norm_gap``: over the leaves, the largest gap between the norm of
  the first step's gradient as AdamW received it and the reference's,
  relative to the larger of the reference leaf's norm and the median
  leaf's;
- ``update_norm_gap``: the same for the norm of the parameters' change
  over the checked steps.

Leaves whose reference gradient norm is under a thousandth of the median
leaf's move by round-off alone and are left out of both leaf gaps.
"""

from __future__ import annotations

import numpy as np

EXACT = ("vocab_mismatch", "sparse_mismatch", "label_mismatch")
DEAD_LEAF = 1e-3


def _mismatch(got, want) -> int:
    got = np.asarray(got)
    if got.shape != want.shape or got.dtype != want.dtype:
        return int(want.size)
    return int(np.count_nonzero(got != want))


def etl_numbers(tables_got, tables_want, batches_got, batches_want) -> dict:
    """Exact counts and the dense relative error over the checked batches;
    the fitted tables are compared in order (none for a stateless
    pipeline)."""
    sparse = label = 0
    dense_err = 0.0
    for got, want in zip(batches_got, batches_want):
        sparse += _mismatch(got["sparse"], want["sparse"])
        label += _mismatch(got["label"], want["label"])
        g = np.asarray(got["dense"], np.float64)
        w = want["dense"].astype(np.float64)
        if g.shape != w.shape:
            dense_err = float("inf")
            continue
        rel = np.abs(g - w) / np.maximum(np.abs(w),
                                         np.finfo(np.float32).tiny)
        dense_err = max(dense_err, float(np.nan_to_num(rel, nan=np.inf).max()))
    vocab = sum(_mismatch(t, w) for t, w in zip(tables_got, tables_want))
    for extra in list(tables_got[len(tables_want):]) + list(
            tables_want[len(tables_got):]):
        vocab += int(np.asarray(extra).size)
    return {"vocab_mismatch": vocab, "sparse_mismatch": sparse,
            "label_mismatch": label, "dense_rel_err": dense_err}


def _leaf_gap(got: dict, want: dict, live: list) -> float:
    med = float(np.median([want[k] for k in want]))
    gap = 0.0
    for k in live:
        g = got.get(k, float("nan"))
        d = abs(g - want[k]) / max(want[k], med)
        gap = max(gap, d if np.isfinite(d) else float("inf"))
    return gap


def step_numbers(prog: dict, ref: dict) -> dict:
    """Loss and leaf-norm gaps of the program's checked steps against the
    reference's (both as ``reference.dlrm.train_steps`` returns them)."""
    losses = [abs(a - b) / abs(b) for a, b in zip(prog["losses"],
                                                   ref["losses"])]
    if len(prog["losses"]) != len(ref["losses"]):
        losses.append(float("inf"))
    loss_gap = max((x if np.isfinite(x) else float("inf")) for x in losses)
    med = float(np.median(list(ref["grad_norms"].values())))
    live = [k for k, v in ref["grad_norms"].items() if v >= DEAD_LEAF * med]
    return {"loss_gap": loss_gap,
            "grad_norm_gap": _leaf_gap(prog["grad_norms"],
                                       ref["grad_norms"], live),
            "update_norm_gap": _leaf_gap(prog["change_norms"],
                                         ref["change_norms"], live),
            "dead_leaves": sorted(set(ref["grad_norms"]) - set(live))}


def judge(numbers: dict, limits: dict) -> tuple:
    """(correct, checks): every compared number beside its limit.  A number
    with no limit in the configuration fails, as does one that is NaN."""
    checks = {}
    ok = True
    for name, value in numbers.items():
        if name == "dead_leaves":
            continue
        limit = 0 if name in EXACT else limits.get(name)
        passed = (limit is not None and value == value and value <= limit)
        ok = ok and passed
        checks[name] = {"value": value, "limit": limit}
    return ok, checks
