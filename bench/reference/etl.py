"""Plain reference of the ETL: the paper's Pipelines I, II and III on a
Criteo-shaped schema (``label``, ``dense_<i>``, ``sparse_<i>`` hex).

Straight numpy, written from the pipelines' published semantics and
independent of the code under test.  A configuration names this module as
its ``etl_reference`` and states the pipeline in
``pipeline.paper_pipeline`` (``which`` and the sizes it uses); every size
the semantics need has to be stated there, none is defaulted.

- dense: NaN -> 0, negatives clamped to 0, log(1 + x), in float32 (the
  log computed in float64 and rounded once), packed at the declared
  padded width with zero padding columns;
- sparse: ASCII hex -> 32-bit two's-complement value, all-zero bytes ->
  INT32_MIN, positive modulus of the pipeline's id range (``modulus`` for
  I, ``small_vocab`` for II, ``large_vocab`` for III).  Pipeline I packs
  that id.  Pipelines II and III fit one vocabulary shared by every
  sparse column: each value seen in the fit stream gets the rank of its
  first appearance (row-major over rows and columns, batches in order),
  and an unseen value maps to the out-of-vocabulary index n_unique;
  packed as int32 at the declared padded width with zero padding columns;
- label: float32, as read.

``dtype=bfloat16`` computes the dense column in bfloat16: the control.
"""

from __future__ import annotations

import numpy as np

INT32_MIN = -(2 ** 31)
_RANGE_KEY = {"I": "modulus", "II": "small_vocab", "III": "large_vocab"}


def _spec(config: dict) -> tuple:
    """(which, id range) of the configuration's paper pipeline."""
    kw = config["pipeline"]["paper_pipeline"]
    which = kw["which"]
    if which not in _RANGE_KEY:
        raise ValueError(f"no reference for paper pipeline {which!r}")
    if not kw.get("fill_missing", True) or kw.get("min_count", 1) != 1:
        raise ValueError("the reference holds fill_missing=True and "
                         "min_count=1 only")
    return which, int(kw[_RANGE_KEY[which]])


def id_rows(config: dict) -> int:
    """Rows an embedding table needs for every id the pipeline packs."""
    which, cap = _spec(config)
    return cap if which == "I" else cap + 1


def table_capacities(config: dict) -> list:
    """Slots of each vocabulary table the pipeline fits (none for I)."""
    which, cap = _spec(config)
    return [] if which == "I" else [cap]


def hex_to_int32(col: np.ndarray) -> np.ndarray:
    """uint8[n, w] ASCII hex -> int64 holding the int32 value."""
    c = col.astype(np.int64)
    digit = np.where(c >= 97, c - 87, np.where(c >= 65, c - 55, c - 48))
    digit = np.where(c == 0, 0, digit)
    val = np.zeros(col.shape[0], np.int64)
    for i in range(col.shape[1]):
        val = val * 16 + digit[:, i]
    val = val % (1 << 32)
    val = np.where(val >= (1 << 31), val - (1 << 32), val)
    return np.where(np.all(col == 0, axis=1), INT32_MIN, val)


def _count(raw: dict, prefix: str) -> int:
    return sum(1 for k in raw if k.startswith(prefix))


def sparse_ids(raw: dict, capacity: int) -> np.ndarray:
    """(rows, sparse columns) ids in [0, capacity)."""
    cols = [hex_to_int32(raw[f"sparse_{i}"])
            for i in range(_count(raw, "sparse_"))]
    return np.mod(np.stack(cols, axis=1), capacity)


def fit(pool: list, config: dict) -> list:
    """The fitted vocabulary tables over ``pool``, in the program's order:
    first-appearance rank of every value, -1 where never seen."""
    which, cap = _spec(config)
    if which == "I":
        return []
    flat = np.concatenate([sparse_ids(b, cap).reshape(-1) for b in pool])
    values, first = np.unique(flat, return_index=True)
    table = np.full(cap, -1, np.int64)
    table[values[np.argsort(first, kind="stable")]] = np.arange(len(values))
    return [table.astype(np.int32)]


def dense(raw: dict, padded: int, dtype=np.float32) -> np.ndarray:
    x = np.stack([raw[f"dense_{i}"] for i in range(_count(raw, "dense_"))],
                 axis=1)
    x = np.where(np.isnan(x), 0.0, x)
    x = np.maximum(x, 0.0)
    if np.dtype(dtype) == np.float32:
        y = np.log1p(x.astype(np.float64)).astype(np.float32)
    else:
        y = np.log1p(x.astype(dtype).astype(np.float32)).astype(dtype)
        y = y.astype(np.float32)
    out = np.zeros((x.shape[0], padded), np.float32)
    out[:, :x.shape[1]] = y
    return out


def apply(raw: dict, tables: list, config: dict, dtype=np.float32) -> dict:
    """One packed batch: {"dense", "sparse", "label"}."""
    which, cap = _spec(config)
    outs = {o["name"]: o["cols"] for o in config["etl_outputs"]}
    ids = sparse_ids(raw, cap)
    if which != "I":
        table, = tables
        hit = table[ids]
        ids = np.where(hit >= 0, hit, int((table >= 0).sum()))
    sp = np.zeros((ids.shape[0], outs["sparse"]), np.int32)
    sp[:, :ids.shape[1]] = ids
    return {"dense": dense(raw, outs["dense"], dtype),
            "sparse": sp,
            "label": raw["label"].astype(np.float32)}
