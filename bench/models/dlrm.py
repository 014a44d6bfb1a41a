"""The system under test's DLRM, built from a configuration.

A configuration names its model kind in ``model``; the harness finds the
plain reference in ``bench/reference/<kind>.py`` (which reads the widths
from the configuration) and the program's model here, in
``bench/models/<kind>.py``, which exposes ``build`` and
``train_flops_per_row``.
"""

from __future__ import annotations

from bench import work


def build(widths: dict):
    """(model config, loss) of the program at the reference's widths; the
    loss looks ``dlrm.loss_fn`` up at call time, where faults patch it."""
    from repro.models import dlrm
    cfg = dlrm.DLRMConfig(
        n_dense=widths["n_dense"], n_sparse=widths["n_sparse"],
        vocab_size=widths["vocab_size"], d_emb=widths["d_emb"],
        bot_mlp=tuple(widths["bot_mlp"]), top_mlp=tuple(widths["top_mlp"]),
        dense_padded=widths["dense_padded"],
        param_dtype=widths["param_dtype"],
        compute_dtype=widths["compute_dtype"])
    return cfg, lambda p, b: dlrm.loss_fn(p, b, cfg)


def train_flops_per_row(widths: dict) -> int:
    return work.dlrm_train_flops_per_row(widths)
