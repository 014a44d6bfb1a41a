"""Traffic generator: raw click-log batches from a mix file and a seed.

One general generator for every mix under ``bench/traffic/``.  The columns
are those of the Criteo-Kaggle shape (``label``, ``dense_<i>``,
``sparse_<i>``); a mix sets how many of each, the hex width of the sparse
ids, their skew and id range, the dense values' distribution, the share of
missing values and of positive labels.

The draws follow the Dataset-I generator of the system under test
(``repro.data.synth.gen_batch``), copied here so that the yardstick cannot
move with the program: dense values are lognormal with a share negated (so
the clamp has work) and NaN where missing; sparse ids are Zipf draws modulo
the id universe (or, with ``"distribution": "uniform"``, uniform over
it), written as lowercase ASCII hex, all-zero bytes where missing; labels
are Bernoulli.
"""

from __future__ import annotations

import json
import os

import numpy as np

_HEX = np.frombuffer(b"0123456789abcdef", dtype=np.uint8)
TRAFFIC_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "traffic")


def load_mix(name: str, traffic_dir: str = TRAFFIC_DIR) -> dict:
    """The mix file ``<traffic_dir>/<name>.json``."""
    with open(os.path.join(traffic_dir, f"{name}.json")) as fh:
        return json.load(fh)


def _hex_encode(vals: np.ndarray, width: int) -> np.ndarray:
    """uint32[n] -> uint8[n, width] lowercase ASCII hex."""
    out = np.empty(vals.shape + (width,), np.uint8)
    v = vals.astype(np.uint64)
    for i in range(width - 1, -1, -1):
        out[..., i] = _HEX[(v & 0xF).astype(np.int64)]
        v >>= np.uint64(4)
    return out


def column_names(mix: dict) -> list:
    sch = mix["schema"]
    return (["label"] + [f"dense_{i}" for i in range(sch["dense_columns"])]
            + [f"sparse_{i}" for i in range(sch["sparse_columns"])])


def gen_batch(mix: dict, rng: np.random.Generator) -> dict:
    """One raw columnar batch of ``mix["batch_rows"]`` rows."""
    n = mix["batch_rows"]
    sch, dense, sparse = mix["schema"], mix["dense"], mix["sparse"]
    miss = mix["missing_rate"]
    if sparse["distribution"] not in ("zipf", "uniform"):
        raise ValueError(f"unknown sparse distribution "
                         f"{sparse['distribution']!r}")
    batch = {"label": (rng.random(n) < mix["label_positive_share"]
                       ).astype(np.float32)}
    for i in range(sch["dense_columns"]):
        x = rng.lognormal(mean=dense["lognormal_mean"],
                          sigma=dense["lognormal_sigma"],
                          size=n).astype(np.float32)
        x = np.where(rng.random(n) < dense["negative_share"], -x, x)
        x[rng.random(n) < miss] = np.nan
        batch[f"dense_{i}"] = x
    for i in range(sch["sparse_columns"]):
        if sparse["distribution"] == "zipf":
            ids = rng.zipf(sparse["zipf_a"], size=n) % sparse["id_universe"]
        else:
            ids = rng.integers(0, sparse["id_universe"], size=n)
        ids = ids.astype(np.uint32)
        col = _hex_encode(ids, sch["hex_width"])
        col[rng.random(n) < miss] = 0
        batch[f"sparse_{i}"] = col
    return batch


def gen_pool(mix: dict, seed: int) -> list:
    """The run's pool: ``mix["pool_batches"]`` batches drawn from ``seed``.

    Every seed gives the same sizes; only the draws differ.
    """
    rng = np.random.default_rng(seed)
    return [gen_batch(mix, rng) for _ in range(mix["pool_batches"])]
