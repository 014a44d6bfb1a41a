"""Pure-jnp oracles for every Pallas kernel in this package.

These define the ground-truth semantics; tests sweep shapes/dtypes and assert
allclose between each kernel (interpret=True on CPU) and these references.
"""

from __future__ import annotations

import jax.numpy as jnp


# ---------------------------------------------------------------------------
# fused stateless stage
# ---------------------------------------------------------------------------

def fused_chain(x, chain_fn):
    """Apply a code-generated elementwise chain to a whole block at once."""
    return chain_fn(x)


def hex2int_digit_major(x):
    """uint8[w, ...] ASCII-hex digit planes -> int32[...] (two's complement).

    All-zero strings (missing) map to operators.INT_MISSING.  Digit planes
    are folded one at a time in int32 (a left shift by 4 and an or are the
    same bits in int32 and uint32), so no op reduces over the digit axis —
    the form Mosaic lowers inside a kernel.
    """
    missing = None
    val = jnp.zeros(x.shape[1:], jnp.int32)
    for i in range(x.shape[0]):
        c = x[i].astype(jnp.int32)
        missing = (c == 0) if missing is None else missing & (c == 0)
        c = jnp.where(c == 0, 48, c)
        dig = jnp.where(c >= 97, c - 87, jnp.where(c >= 65, c - 55, c - 48))
        val = (val << 4) | dig
    return jnp.where(missing, jnp.int32(-(2 ** 31)), val)


# ---------------------------------------------------------------------------
# vocabulary build / lookup
# ---------------------------------------------------------------------------

def vocab_build_chunk(values, capacity):
    """First-occurrence position of each value within one chunk.

    values: int32[n] (or any shape, read row-major) in [0, capacity).
    Returns int32[capacity], with 2**31 - 1 marking "absent in this chunk".
    """
    values = values.reshape(-1)
    n = values.shape[0]
    init = jnp.full((capacity,), jnp.int32(2 ** 31 - 1))
    pos = jnp.arange(n, dtype=jnp.int32)
    return init.at[values].min(pos)


ABSENT32 = 2 ** 31 - 1


def vocab_state_init(capacity):
    """Global fit state: (first_chunk, pos_in_chunk, counts), all int32.

    Positions are 64-bit in spirit but TPU/Pallas has no int64; the stream is
    processed in monotonically increasing chunks, so (chunk_idx, pos32) orders
    identically to a global 64-bit position.  counts back the paper's
    frequency-based filtering (§3.2.2).
    """
    return (jnp.full((capacity,), ABSENT32, jnp.int32),
            jnp.full((capacity,), ABSENT32, jnp.int32),
            jnp.zeros((capacity,), jnp.int32))


def vocab_counts_chunk(values, capacity):
    """Occurrence counts of one chunk (int32[capacity])."""
    return jnp.bincount(values, length=capacity).astype(jnp.int32)


def vocab_merge(state, chunk_first_pos, chunk_idx, chunk_counts=None):
    """Merge one chunk's first-pos (+counts). Chunks MUST arrive in
    increasing order, so a value seen before keeps its record; only absent
    slots are filled."""
    first_chunk, pos, counts = state
    newly = (first_chunk == ABSENT32) & (chunk_first_pos != ABSENT32)
    first_chunk = jnp.where(newly, jnp.int32(chunk_idx), first_chunk)
    pos = jnp.where(newly, chunk_first_pos, pos)
    if chunk_counts is not None:
        counts = counts + chunk_counts
    return first_chunk, pos, counts


def vocab_finalize(state, min_count: int = 1):
    """(first_chunk, pos, counts) -> int32 rank table (-1 = absent/filtered).

    min_count > 1 drops rare values (frequency filter): they rank as absent
    and map to the OOV index at apply time."""
    first_chunk, pos, counts = state
    capacity = first_chunk.shape[0]
    present = first_chunk != ABSENT32
    if min_count > 1:  # frequency filter is opt-in; counts optional otherwise
        present = present & (counts >= min_count)
    key_chunk = jnp.where(present, first_chunk, ABSENT32)
    order = jnp.lexsort((pos, key_chunk))  # chunk major, pos minor
    rank = jnp.zeros(capacity, jnp.int32).at[order].set(
        jnp.arange(capacity, dtype=jnp.int32))
    return jnp.where(present, rank, -1).astype(jnp.int32)


def vocab_lookup(x, table, n_unique):
    """Map x through table; absent (-1) entries map to the OOV index n_unique."""
    hit = table[x]
    return jnp.where(hit >= 0, hit, n_unique).astype(jnp.int32)


# ---------------------------------------------------------------------------
# format-aware packer
# ---------------------------------------------------------------------------

def pack_blocks(blocks, out_dtype, pad_cols_to=1):
    """Concat column blocks along axis 1, cast, pad width to a multiple.

    blocks: list of [rows, c_i] arrays. Output [rows, padded(sum c_i)].
    """
    rows = blocks[0].shape[0]
    cat = jnp.concatenate([b.astype(out_dtype) for b in blocks], axis=1)
    total = cat.shape[1]
    padded = -(-total // pad_cols_to) * pad_cols_to
    if padded != total:
        cat = jnp.pad(cat, ((0, 0), (0, padded - total)))
    assert cat.shape == (rows, padded)
    return cat


# ---------------------------------------------------------------------------
# embedding bag (DLRM trainer-side hot spot)
# ---------------------------------------------------------------------------

def embedding_bag(table, indices, weights=None):
    """Sum-pool embedding rows: out[b] = sum_k w[b,k] * table[idx[b,k]].

    table: [vocab, dim]; indices: int32[batch, nnz]; weights: [batch, nnz] or
    None.  ``-1`` indices are sentinels (padding lanes) and contribute zero.
    """
    valid = indices >= 0
    rows = table[jnp.where(valid, indices, 0)]  # [batch, nnz, dim]
    rows = jnp.where(valid[..., None], rows, 0)
    if weights is not None:
        rows = rows * weights[..., None].astype(rows.dtype)
    return rows.sum(axis=1)


def embedding_bag_cached(table, cache, slot_idx, cold_idx=None):
    """Two-level oracle: hot entries (slot >= 0) read ``cache[slot]``, cold
    entries read ``table[cold]``, double-blank entries contribute zero."""
    hot = slot_idx >= 0
    rows = jnp.where(hot[..., None], cache[jnp.where(hot, slot_idx, 0)], 0)
    if cold_idx is not None:
        cold_ok = (~hot) & (cold_idx >= 0)
        rows = jnp.where(cold_ok[..., None],
                         table[jnp.where(cold_ok, cold_idx, 0)], rows)
    return rows.sum(axis=1)


def embedding_bag_grad_table(table_shape, indices, grad_out, weights=None):
    """Gradient of embedding_bag wrt table (scatter-add)."""
    vocab, dim = table_shape
    batch, nnz = indices.shape
    g = jnp.broadcast_to(grad_out[:, None, :], (batch, nnz, dim))
    if weights is not None:
        g = g * weights[..., None].astype(g.dtype)
    flat_idx = indices.reshape(-1)
    flat_g = g.reshape(-1, dim)
    return jnp.zeros((vocab, dim), grad_out.dtype).at[flat_idx].add(flat_g)
