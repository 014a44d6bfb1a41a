"""Pallas kernels vs ref.py oracles: shape/dtype sweeps (interpret=True)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import operators as O
from repro.kernels import ops, ref

RNG = np.random.default_rng(7)
HEXMAP = np.frombuffer(b"0123456789abcdef", np.uint8)


@pytest.mark.parametrize("rows,cols", [(8, 13), (100, 26), (257, 5), (1024, 128)])
@pytest.mark.parametrize("dtype", [np.float32])
def test_fused_dense_sweep(rows, cols, dtype):
    x = (RNG.normal(size=(rows, cols)) * 10).astype(dtype)
    clamp, log = O.Clamp(0.0), O.Logarithm()
    chain = lambda v: log.jnp_expr(clamp.jnp_expr(v))
    fn = ops.fused_stage(chain, in_dtype=dtype, out_dtype=dtype,
                         interpret=True)
    got = np.asarray(fn(jnp.asarray(x)))
    want = np.asarray(ref.fused_chain(jnp.asarray(x), chain))
    np.testing.assert_allclose(got, want, rtol=1e-6)


@pytest.mark.parametrize("rows,cols,width", [(64, 26, 8), (100, 3, 4), (8, 1, 8)])
def test_fused_hex_sweep(rows, cols, width):
    digits = RNG.integers(0, 16, size=(width, rows, cols))
    raw = HEXMAP[digits]
    mod = O.Modulus(4096)
    chain = lambda v: mod.jnp_expr(ref.hex2int_digit_major(v))
    fn = ops.fused_stage(chain, in_dtype=np.uint8, out_dtype=np.int32,
                         hex_width=width, interpret=True)
    got = np.asarray(fn(jnp.asarray(raw)))
    # oracle: trailing-hex layout numpy
    want = mod.numpy(O.Hex2Int(width).numpy(np.moveaxis(raw, 0, -1)))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("cap,parts", [(64, 1), (64, 4), (256, 8), (512, 2)])
@pytest.mark.parametrize("n", [1, 100, 5000])
def test_vocab_build_sweep(cap, parts, n):
    vals = RNG.integers(0, cap, size=(n,)).astype(np.int32)
    got = np.asarray(ops.vocab_build_chunk(jnp.asarray(vals), capacity=cap,
                                           partitions=parts, interpret=True))
    want = np.asarray(ref.vocab_build_chunk(jnp.asarray(vals), cap))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("rows,width,cap", [(8, 3, 64), (100, 7, 128),
                                            (257, 1, 32)])
@pytest.mark.parametrize("partitions", [1, 4])
def test_fit_dataflow_matches_staged_build(rows, width, cap, partitions):
    """Fused fit kernel == staged build kernel + counts oracle, including
    out-of-range values: negatives and >= capacity drop on both paths
    (regression: JAX scatter index normalization must not wrap -1 to the
    last table slot).  The staged build agrees at every partition count."""
    from repro.kernels.dataflow import StreamInput, make_fit_dataflow

    vals = RNG.integers(0, cap, size=(rows, width)).astype(np.int32)
    vals.reshape(-1)[:: max(1, vals.size // 7)] = -1       # missing ids
    if vals.size > 3:
        vals.reshape(-1)[1] = cap + 5                      # overflow id
    fn = make_fit_dataflow([StreamInput("v", width, np.dtype(np.int32))],
                           [], "v", cap, interpret=True)
    got_fp, got_cnt = (np.asarray(a) for a in fn(jnp.asarray(vals)))
    flat = vals.reshape(-1)
    want_fp = np.full(cap, 2 ** 31 - 1, np.int32)
    want_cnt = np.zeros(cap, np.int32)
    for i, v in enumerate(flat):
        if 0 <= v < cap:
            want_fp[v] = min(want_fp[v], i)
            want_cnt[v] += 1
    np.testing.assert_array_equal(got_fp, want_fp)
    np.testing.assert_array_equal(got_cnt, want_cnt)
    # the staged Pallas build drops out-of-range values too: bit-equal
    staged = np.asarray(ops.vocab_build_chunk(
        jnp.asarray(vals), capacity=cap, partitions=partitions,
        interpret=True))
    np.testing.assert_array_equal(got_fp, staged)


@pytest.mark.parametrize("rows,cols,cap,parts", [(8, 3, 64, 4), (100, 26, 128, 1),
                                                 (33, 7, 256, 8)])
def test_vocab_lookup_sweep(rows, cols, cap, parts):
    vals = RNG.integers(0, cap, size=(500,)).astype(np.int32)
    vg = O.VocabGen(cap)
    table = vg.finalize(vg.update(vg.init_state(), vals, 0))
    n = O.VocabGen.n_unique(table)
    x = RNG.integers(0, cap, size=(rows, cols)).astype(np.int32)
    got = np.asarray(ops.vocab_lookup(jnp.asarray(x), jnp.asarray(table), n,
                                      partitions=parts, interpret=True))
    want = np.asarray(ref.vocab_lookup(jnp.asarray(x), jnp.asarray(table), n))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("widths,out_dtype", [
    ([13, 26], np.float32), ([1], np.float32), ([5, 7, 11], np.int32)])
@pytest.mark.parametrize("rows", [8, 100])
def test_packer_sweep(widths, out_dtype, rows):
    blocks = [(RNG.normal(size=(rows, w)) * 3).astype(np.float32)
              for w in widths]
    pk = ops.packer(widths, [np.float32] * len(widths), out_dtype,
                    pad_cols_to=128, interpret=True)
    got = np.asarray(pk(*[jnp.asarray(b) for b in blocks]))
    want = np.asarray(ref.pack_blocks([jnp.asarray(b) for b in blocks],
                                      out_dtype, 128))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert got.shape[1] % 128 == 0


@pytest.mark.parametrize("rows", [8, 100, 257])
@pytest.mark.parametrize("pad_to", [8, 32])
def test_output_dataflow_sweep(rows, pad_to):
    """One streaming kernel = chain + hex decode + lookup + pack epilogue."""
    from repro.kernels.dataflow import StreamInput, TableInput, TileStep

    dense = (RNG.normal(size=(rows, 5)) * 10).astype(np.float32)
    digits = RNG.integers(0, 16, size=(4, rows, 3))
    hexraw = HEXMAP[digits]
    cap = 64
    vals = RNG.integers(0, cap, size=(500,)).astype(np.int32)
    vg = O.VocabGen(cap)
    table = vg.finalize(vg.update(vg.init_state(), vals, 0))
    n_uniq = O.VocabGen.n_unique(table)

    clamp, log, mod = O.Clamp(0.0), O.Logarithm(), O.Modulus(cap)
    dense_chain = lambda v: log.jnp_expr(clamp.jnp_expr(v))
    hex_chain = lambda v: mod.jnp_expr(ref.hex2int_digit_major(v))

    fn = ops.output_dataflow(
        inputs=[StreamInput("d", 5, np.dtype(np.float32)),
                StreamInput("h", 3, np.dtype(np.uint8), hex_width=4)],
        tables=[TableInput("v0", cap)],
        steps=[TileStep("map", "dlog", ("d",), fn=dense_chain),
               TileStep("map", "hid", ("h",), fn=hex_chain),
               TileStep("lookup", "hrank", ("hid",), table=0)],
        terminals=[("dlog", 5), ("hrank", 3)],
        out_dtype=np.float32, pad_cols_to=pad_to, interpret=True)
    # the compiler folds OOV into the table before the call
    resolved = np.where(table >= 0, table, n_uniq).astype(np.int32)
    got = np.asarray(fn(jnp.asarray(dense), jnp.asarray(hexraw),
                        jnp.asarray(resolved).reshape(1, -1)))

    want_d = np.asarray(dense_chain(jnp.asarray(dense)))
    want_ids = mod.numpy(O.Hex2Int(4).numpy(np.moveaxis(hexraw, 0, -1)))
    want_r = np.asarray(ref.vocab_lookup(jnp.asarray(want_ids),
                                         jnp.asarray(table), n_uniq))
    want = np.asarray(ref.pack_blocks(
        [jnp.asarray(want_d), jnp.asarray(want_r)], np.float32, pad_to))
    assert got.shape == (rows, -(-8 // pad_to) * pad_to)
    np.testing.assert_allclose(got, want, rtol=1e-6)


@pytest.mark.parametrize("vocab,dim,batch,nnz,parts", [
    (64, 16, 33, 5, 4), (128, 32, 8, 1, 1), (256, 8, 100, 7, 8)])
def test_embedding_bag_sweep(vocab, dim, batch, nnz, parts):
    tbl = RNG.normal(size=(vocab, dim)).astype(np.float32)
    idx = RNG.integers(0, vocab, size=(batch, nnz)).astype(np.int32)
    got = np.asarray(ops.embedding_bag(jnp.asarray(tbl), jnp.asarray(idx),
                                       partitions=parts, interpret=True))
    want = np.asarray(ref.embedding_bag(jnp.asarray(tbl), jnp.asarray(idx)))
    # partition accumulation reorders the f32 sums
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("vocab,parts", [
    (67, 4),    # vocab does not divide partitions: last partition padded
    (100, 8),   # 100 // 8 leaves a ragged tail
    (33, 1)])
def test_embedding_bag_padded_partition(vocab, parts):
    """Arbitrary vocab sizes work with partitions > 1 (the wrapper pads the
    last partition; padded rows are unreachable)."""
    tbl = RNG.normal(size=(vocab, 12)).astype(np.float32)
    idx = RNG.integers(0, vocab, size=(50, 4)).astype(np.int32)
    got = np.asarray(ops.embedding_bag(jnp.asarray(tbl), jnp.asarray(idx),
                                       partitions=parts, interpret=True))
    want = np.asarray(ref.embedding_bag(jnp.asarray(tbl), jnp.asarray(idx)))
    # gather-then-pool structure: identical rows, identical sum order
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("batch,nnz,block_batch", [
    (33, 5, 8),    # batch not a multiple of block_batch
    (7, 1, 128),   # nnz=1, tiny batch below the block
    (129, 3, 128)])  # one full block + a remainder row
def test_embedding_bag_sentinels_and_ragged_batch(batch, nnz, block_batch):
    """-1 sentinel indices contribute zero (incl. fully-empty bags) and
    batch padding never leaks into the output."""
    from repro.kernels import embedding_bag as bag
    vocab = 90
    tbl = RNG.normal(size=(vocab, 16)).astype(np.float32)
    idx = RNG.integers(0, vocab, size=(batch, nnz)).astype(np.int32)
    idx[RNG.random(idx.shape) < 0.3] = -1
    idx[0, :] = -1  # an entirely-empty bag pools to the zero vector
    got = np.asarray(bag.embedding_bag(jnp.asarray(tbl), jnp.asarray(idx),
                                       partitions=3, block_batch=block_batch,
                                       interpret=True))
    want = np.asarray(ref.embedding_bag(jnp.asarray(tbl), jnp.asarray(idx)))
    assert got.shape == (batch, 16)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got[0], 0.0)


@pytest.mark.parametrize("parts", [1, 4])
@pytest.mark.parametrize("staged", [False, True])
def test_embedding_bag_cached_bit_equal_to_uncached(parts, staged):
    """Two-level cached kernel == uncached kernel, bit for bit, when the
    cache rows mirror the table rows the remap assigned (the lookahead
    stage's invariant).  ``staged`` covers the single-pass fast path."""
    vocab, dim, batch, nnz, cache_rows = 150, 8, 40, 6, 32
    tbl = RNG.normal(size=(vocab, dim)).astype(np.float32)
    idx = RNG.integers(0, vocab, size=(batch, nnz)).astype(np.int32)
    idx[RNG.random(idx.shape) < 0.1] = -1

    if staged:
        # stage EVERY distinct row into an ext cache: cold_idx=None
        uniq = np.unique(idx[idx >= 0])
        cache = tbl[uniq]
        slot_of = np.full(vocab, -1, np.int64)
        slot_of[uniq] = np.arange(len(uniq))
        slot = np.where(idx >= 0, slot_of[idx.clip(min=0)], -1).astype(np.int32)
        cold = None
    else:
        hot = RNG.choice(vocab, size=cache_rows, replace=False)
        cache = tbl[hot]
        slot_of = np.full(vocab, -1, np.int64)
        slot_of[hot] = np.arange(cache_rows)
        slot = np.where(idx >= 0, slot_of[idx.clip(min=0)], -1).astype(np.int32)
        cold = np.where(slot < 0, idx, -1).astype(np.int32)

    got = np.asarray(ops.embedding_bag_cached(
        jnp.asarray(tbl), jnp.asarray(cache), jnp.asarray(slot),
        None if cold is None else jnp.asarray(cold),
        partitions=parts, interpret=True))
    want = np.asarray(ops.embedding_bag(jnp.asarray(tbl), jnp.asarray(idx),
                                        partitions=parts, interpret=True))
    np.testing.assert_array_equal(got, want)
    want_ref = np.asarray(ref.embedding_bag_cached(
        jnp.asarray(tbl), jnp.asarray(cache), jnp.asarray(slot),
        None if cold is None else jnp.asarray(cold)))
    np.testing.assert_array_equal(got, want_ref)


def test_flash_attention_matches_dense():
    from repro.models import layers as L
    B, S, H, D = 2, 128, 2, 16
    q, k, v = (jnp.asarray(RNG.normal(size=(B, S, H, D)).astype(np.float32))
               for _ in range(3))
    qp = kp = jnp.arange(S)
    for causal, window in [(True, 0), (True, 32), (False, 0)]:
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
        s = s + L._mask_from_positions(qp, kp, causal, window)
        want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
        got = L.flash_attention(q, k, v, qp, kp, causal=causal, window=window,
                                q_chunk=32, k_chunk=16)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)
