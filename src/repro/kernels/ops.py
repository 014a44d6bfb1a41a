"""Jit'd public wrappers over the Pallas kernels.

``interpret=None`` on every wrapper resolves through
``kernels.backend.default_interpret`` — compiled mode (interpret=False)
whenever the default JAX backend is a TPU (Mosaic), interpret mode
otherwise.  The kernel modules apply the same
default themselves; the wrappers resolve eagerly only so the jit static
argnames see a concrete bool.
"""

from __future__ import annotations

import functools

import jax

from repro.kernels import dataflow as _dataflow
from repro.kernels import embedding_bag as _bag
from repro.kernels import vocab as _vocab
from repro.kernels.backend import compiled_backend, default_interpret

__all__ = [
    "compiled_backend", "default_interpret",
    "fused_stage", "output_dataflow", "group_dataflow", "fit_dataflow",
    "vocab_build_chunk", "vocab_lookup", "packer",
    "embedding_bag", "embedding_bag_cached",
]


def fused_stage(chain_fn, *, in_dtype, out_dtype, hex_width=0,
                block_rows=256, block_cols=512, interpret=None):
    if interpret is None:
        interpret = default_interpret()
    return _dataflow.make_fused_stage(
        chain_fn, in_dtype=in_dtype, out_dtype=out_dtype, hex_width=hex_width,
        block_rows=block_rows, block_cols=block_cols, interpret=interpret)


def output_dataflow(inputs, tables, steps, terminals, out_dtype, *,
                    pad_cols_to=1, block_rows=256, interpret=None,
                    vmem_limit_bytes=None):
    """One PackOutput's full streaming program as a single Pallas kernel."""
    if interpret is None:
        interpret = default_interpret()
    return jax.jit(_dataflow.make_output_dataflow(
        inputs, tables, steps, terminals, out_dtype,
        pad_cols_to=pad_cols_to, block_rows=block_rows, interpret=interpret,
        vmem_limit_bytes=vmem_limit_bytes))


def group_dataflow(inputs, tables, steps, outputs, *,
                   block_rows=256, interpret=None, vmem_limit_bytes=None):
    """A DataflowGroup's merged streaming program — several PackOutputs'
    packed blocks from a single Pallas kernel."""
    if interpret is None:
        interpret = default_interpret()
    return jax.jit(_dataflow.make_group_dataflow(
        inputs, tables, steps, outputs, block_rows=block_rows,
        interpret=interpret, vmem_limit_bytes=vmem_limit_bytes))


def fit_dataflow(inputs, steps, value_buf, capacity, *,
                 block_rows=256, interpret=None, vmem_limit_bytes=None):
    """One VocabFit's full fit chunk (decode + bound + first-pos/count
    build) as a single Pallas kernel."""
    if interpret is None:
        interpret = default_interpret()
    return jax.jit(_dataflow.make_fit_dataflow(
        inputs, steps, value_buf, capacity, block_rows=block_rows,
        interpret=interpret, vmem_limit_bytes=vmem_limit_bytes))


@functools.partial(jax.jit, static_argnames=("capacity", "partitions", "interpret"))
def vocab_build_chunk(values, *, capacity, partitions=1, interpret=None):
    if interpret is None:
        interpret = default_interpret()
    return _vocab.vocab_build_chunk(values, capacity, partitions=partitions,
                                    interpret=interpret)


@functools.partial(jax.jit, static_argnames=("partitions", "interpret"))
def vocab_lookup(x, table, n_unique, *, partitions=1, interpret=None):
    if interpret is None:
        interpret = default_interpret()
    return _vocab.vocab_lookup(x, table, n_unique, partitions=partitions,
                               interpret=interpret)


def packer(col_widths, in_dtypes, out_dtype, *, pad_cols_to=128,
           block_rows=256, interpret=None):
    if interpret is None:
        interpret = default_interpret()
    return jax.jit(_dataflow.make_packer(
        col_widths, in_dtypes, out_dtype, pad_cols_to=pad_cols_to,
        block_rows=block_rows, interpret=interpret))


@functools.partial(jax.jit, static_argnames=("partitions", "interpret"))
def embedding_bag(table, indices, *, partitions=1, interpret=None):
    if interpret is None:
        interpret = default_interpret()
    return _bag.embedding_bag(table, indices, partitions=partitions,
                              interpret=interpret)


@functools.partial(jax.jit, static_argnames=("partitions", "interpret"))
def embedding_bag_cached(table, cache, slot_idx, cold_idx=None, *,
                         partitions=1, interpret=None):
    """Two-level cached bag: hot slots from the VMEM cache, cold indices
    through the partitioned table pass (``cold_idx=None`` = fully staged)."""
    if interpret is None:
        interpret = default_interpret()
    return _bag.embedding_bag_cached(table, cache, slot_idx, cold_idx,
                                     partitions=partitions,
                                     interpret=interpret)
