"""Handoff of packed batches from the ETL engine to the trainer.

``put_packed`` places a packed batch on the trainer's devices, sharded
along rows over the mesh's data axes (``batch_sharding``), so the batch
already has the layout ``train_step`` declares in ``in_shardings``.  The
trainer may then donate it (``jit_train_step(..., donate_batch=True)``).
Whether this path avoids copies and reshards on a TPU has not been
measured.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def batch_sharding(mesh: Optional[Mesh], data_axes=("pod", "data")) -> Optional[NamedSharding]:
    """Row-sharded (batch-dim) placement over the data axes of the mesh."""
    if mesh is None:
        return None
    axes = tuple(a for a in data_axes if a in mesh.axis_names)
    return NamedSharding(mesh, P(axes if len(axes) > 1 else axes[0]))


def donation_ready(batch: dict) -> bool:
    """True when every value is a jax.Array the trainer can donate.

    ``put_packed`` output always satisfies this; host numpy batches do not
    (XLA copies them on dispatch, so donation would be meaningless).  Pair
    with ``jit_train_step(..., donate_batch=True)``.
    """
    return all(isinstance(v, jax.Array) for v in batch.values())


def put_packed(batch: dict, sharding: Optional[NamedSharding]) -> dict:
    """Place a packed batch onto the mesh, sharded along rows (batch dim).

    The returned arrays are committed device buffers in the trainer's
    declared layout, so a ``donate_argnums`` train step may take them.
    """
    if sharding is None:
        return {k: jax.device_put(v) for k, v in batch.items()}
    out = {}
    for k, v in batch.items():
        spec = sharding.spec
        nd = np.ndim(v)
        row_spec = P(*( (spec[0],) + (None,) * (nd - 1) ))
        out[k] = jax.device_put(v, NamedSharding(sharding.mesh, row_spec))
    return out


def transfer_stats(batch: dict) -> dict:
    """Bytes moved for the Fig-11 style transfer micro-benchmark."""
    total = 0
    for v in batch.values():
        total += np.dtype(v.dtype).itemsize * int(np.prod(np.shape(v)))
    return {"bytes": total}
