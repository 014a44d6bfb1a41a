"""Work counts that the rooflines and utilizations divide by.

Both counts depend only on a configuration file: the DLRM widths (as the
reference reads them from it) for the model's operations, and the
pipeline's schema, declared outputs and vocabulary capacities for the
ETL's bytes.  No kernel's layout or padding enters, so every
implementation of the same configuration is held to the same work.
"""

from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind is
    an error, never a default."""
    with open(path) as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"{os.path.basename(path)}")
    return table[device_kind]


def _mlp_macs(dims) -> int:
    return sum(a * b for a, b in zip(dims[:-1], dims[1:]))


def dlrm_forward_macs_per_row(model: dict) -> dict:
    """Multiply-accumulates of one row's forward pass, by part.

    Bottom MLP from the packed (padded) dense width; the dot interaction
    as the model computes it, the full (F+1) x (F+1) Gram matrix of the
    bottom output and the F embeddings; the top MLP from the bottom width
    plus the F(F+1)/2 pairs above the diagonal.  Embedding lookups are
    gathers, not operations.
    """
    d, f = model["d_emb"], model["n_sparse"]
    bot = _mlp_macs([model["dense_padded"]] + list(model["bot_mlp"]))
    inter = (f + 1) * (f + 1) * d
    top_in = model["bot_mlp"][-1] + f * (f + 1) // 2
    top = _mlp_macs([top_in] + list(model["top_mlp"]))
    return {"bottom": bot, "interaction": inter, "top": top,
            "total": bot + inter + top}


def dlrm_train_flops_per_row(model: dict) -> int:
    """Forward and backward operations per row: 2 per MAC forward, and the
    backward pass twice the forward."""
    return 3 * 2 * dlrm_forward_macs_per_row(model)["total"]


def etl_bytes_per_batch(config: dict, mix: dict, capacities) -> dict:
    """Bytes the pipeline has to move for one batch, from its schema and
    declared outputs: every raw column read once, every packed output
    written once at its declared padded width, and each vocabulary table
    (int32, one slot per id of its capacity, ``capacities`` as the ETL
    reference states them) read once."""
    rows = config["pipeline"]["paper_pipeline"]["batch_size"]
    sch = mix["schema"]
    raw_row = 4 + 4 * sch["dense_columns"] + sch["hex_width"] * sch["sparse_columns"]
    out_row = 0
    for out in config["etl_outputs"]:
        out_row += out["itemsize"] * out["cols"]
    tables = sum(4 * cap for cap in capacities)
    return {"raw": rows * raw_row, "packed": rows * out_row, "tables": tables,
            "total": rows * (raw_row + out_row) + tables}
