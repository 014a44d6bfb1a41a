"""Compiled (interpret=False) vs interpret-mode kernels: capability
resolution, trace legality, bit-exact parity, and the mosaic-illegal
planner fallback.

Two tiers, gated by what this host can actually do:

- everywhere: ``default_interpret`` capability resolution, trace smokes
  (every kernel entry point traces with ``interpret=False`` — Pallas
  traces the kernel body and index maps at bind time, so shape/layout
  bugs in the compiled path surface even on CPU), the serialized fit
  build against the reference scatter build, the planner's
  ``mosaic-illegal`` fallback, and traced-kernel-count parity between
  modes.
- compiled target present (a TPU): the full bit-equality sweep — every
  entry point, edge rows included (negative / OOV / padding).  The
  compile-only tier for a described v5e lives in ``test_tpu_compile.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import operators as O
from repro.kernels import lanes, ops, ref
from repro.kernels.backend import compiled_backend, default_interpret
from repro.kernels.dataflow import (GroupOutput, StreamInput, TableInput,
                                    TileStep, make_fit_dataflow)

RNG = np.random.default_rng(11)
HEXMAP = np.frombuffer(b"0123456789abcdef", np.uint8)

needs_compiled = pytest.mark.skipif(
    compiled_backend() is None,
    reason="no compiled Pallas target on this backend "
           f"({jax.default_backend()}): parity needs real execution")


# ---------------------------------------------------------------------------
# kernel entry-point cases: name -> (callable, args) builder
#
# Every case covers edge rows: -1 sentinels, out-of-range (OOV) ids, and
# row counts that leave padding in the last tile.
# ---------------------------------------------------------------------------

def _vocab_table(cap: int, seed: int = 3):
    vals = np.random.default_rng(seed).integers(0, cap, size=500).astype(np.int32)
    vg = O.VocabGen(cap)
    table = vg.finalize(vg.update(vg.init_state(), vals, 0))
    return table, O.VocabGen.n_unique(table)


def case_fused_stage(interpret):
    x = (RNG.normal(size=(101, 13)) * 10).astype(np.float32)
    clamp, log = O.Clamp(0.0), O.Logarithm()
    chain = lambda v: log.jnp_expr(clamp.jnp_expr(v))
    fn = ops.fused_stage(chain, in_dtype=np.float32, out_dtype=np.float32,
                         interpret=interpret)
    return fn, (jnp.asarray(x),)


def case_fused_stage_hex(interpret):
    digits = RNG.integers(0, 16, size=(8, 67, 3))
    raw = HEXMAP[digits]
    mod = O.Modulus(4096)
    chain = lambda v: mod.jnp_expr(ref.hex2int_digit_major(v))
    fn = ops.fused_stage(chain, in_dtype=np.uint8, out_dtype=np.int32,
                         hex_width=8, interpret=interpret)
    return fn, (jnp.asarray(raw),)


def case_packer(interpret):
    widths = [13, 26, 5]
    blocks = [jnp.asarray((RNG.normal(size=(77, w)) * 3).astype(np.float32))
              for w in widths]
    fn = ops.packer(widths, [np.float32] * 3, np.float32, pad_cols_to=128,
                    interpret=interpret)
    return fn, tuple(blocks)


def case_output_dataflow(interpret):
    cap = 64
    table, n_uniq = _vocab_table(cap)
    resolved = np.where(table >= 0, table, n_uniq).astype(np.int32)
    dense = (RNG.normal(size=(93, 5)) * 10).astype(np.float32)
    ids = RNG.integers(-1, cap + 3, size=(93, 3)).astype(np.int32)  # OOV rows
    ids_b = np.clip(ids, 0, cap - 1)
    clamp, log = O.Clamp(0.0), O.Logarithm()
    dense_chain = lambda v: log.jnp_expr(clamp.jnp_expr(v))
    fn = ops.output_dataflow(
        inputs=[StreamInput("d", 5, np.dtype(np.float32)),
                StreamInput("i", 3, np.dtype(np.int32))],
        tables=[TableInput("v0", cap)],
        steps=[TileStep("map", "dlog", ("d",), fn=dense_chain),
               TileStep("lookup", "rank", ("i",), table=0),
               TileStep("map", "oh", ("i",),
                        fn=lambda x: lanes.onehot_lanes(x % 4, 4))],
        terminals=[("dlog", 5), ("rank", 3), ("oh", 12)],
        out_dtype=np.float32, pad_cols_to=32, interpret=interpret)
    return fn, (jnp.asarray(dense), jnp.asarray(ids_b),
                jnp.asarray(resolved).reshape(1, -1))


def case_group_dataflow(interpret):
    cap = 64
    table, n_uniq = _vocab_table(cap)
    resolved = np.where(table >= 0, table, n_uniq).astype(np.int32)
    dense = (RNG.normal(size=(57, 5)) * 10).astype(np.float32)
    ids = RNG.integers(0, cap, size=(57, 3)).astype(np.int32)
    clamp, log = O.Clamp(0.0), O.Logarithm()
    dense_chain = lambda v: log.jnp_expr(clamp.jnp_expr(v))
    fn = ops.group_dataflow(
        inputs=[StreamInput("d", 5, np.dtype(np.float32)),
                StreamInput("i", 3, np.dtype(np.int32))],
        tables=[TableInput("v0", cap)],
        steps=[TileStep("map", "dlog", ("d",), fn=dense_chain),
               TileStep("lookup", "rank", ("i",), table=0)],
        outputs=[GroupOutput("a", (("dlog", 5),), np.dtype(np.float32), 16),
                 GroupOutput("b", (("rank", 3),), np.dtype(np.int32), 8)],
        interpret=interpret)
    return fn, (jnp.asarray(dense), jnp.asarray(ids),
                jnp.asarray(resolved).reshape(1, -1))


def case_fit_dataflow(interpret):
    cap = 96
    vals = RNG.integers(0, cap, size=(203, 3)).astype(np.int32)
    vals.reshape(-1)[::11] = -1          # missing ids drop
    vals.reshape(-1)[1] = cap + 7        # overflow ids drop
    fn = ops.fit_dataflow([StreamInput("v", 3, np.dtype(np.int32))],
                          [], "v", cap, interpret=interpret)
    return fn, (jnp.asarray(vals),)


def case_vocab_build(interpret):
    vals = RNG.integers(0, 96, size=777).astype(np.int32)
    fn = lambda v: ops.vocab_build_chunk(v, capacity=96, partitions=3,
                                         interpret=interpret)
    return fn, (jnp.asarray(vals),)


def case_vocab_lookup(interpret):
    cap = 96
    table, n_uniq = _vocab_table(cap)
    x = RNG.integers(0, cap, size=(61, 5)).astype(np.int32)
    fn = lambda a, t: ops.vocab_lookup(a, t, n_uniq, partitions=3,
                                       interpret=interpret)
    return fn, (jnp.asarray(x), jnp.asarray(table))


def case_embedding_bag(interpret):
    tbl = RNG.normal(size=(67, 19)).astype(np.float32)
    idx = RNG.integers(-1, 67, size=(45, 7)).astype(np.int32)  # -1 padding
    fn = lambda t, i: ops.embedding_bag(t, i, partitions=3,
                                        interpret=interpret)
    return fn, (jnp.asarray(tbl), jnp.asarray(idx))


def _cached_bag_inputs():
    vocab, dim, cache_rows = 67, 19, 11
    tbl = RNG.normal(size=(vocab, dim)).astype(np.float32)
    idx = RNG.integers(-1, vocab, size=(45, 7)).astype(np.int32)
    hot = np.random.default_rng(5).choice(vocab, size=cache_rows, replace=False)
    slotmap = {int(v): s for s, v in enumerate(hot)}
    cache = tbl[hot]
    slot = np.vectorize(lambda v: slotmap.get(int(v), -1))(idx).astype(np.int32)
    cold = np.where((idx >= 0) & (slot < 0), idx, -1).astype(np.int32)
    return tbl, cache, slot, cold


def case_embedding_bag_cached(interpret):
    tbl, cache, slot, cold = _cached_bag_inputs()
    fn = lambda t, c, s, o: ops.embedding_bag_cached(
        t, c, s, o, partitions=3, interpret=interpret)
    return fn, (jnp.asarray(tbl), jnp.asarray(cache),
                jnp.asarray(slot), jnp.asarray(cold))


def case_embedding_bag_cache_only(interpret):
    tbl, cache, slot, _ = _cached_bag_inputs()
    fn = lambda t, c, s: ops.embedding_bag_cached(t, c, s, None,
                                                  interpret=interpret)
    return fn, (jnp.asarray(tbl), jnp.asarray(cache), jnp.asarray(slot))


CASES = [
    case_fused_stage, case_fused_stage_hex, case_packer,
    case_output_dataflow, case_group_dataflow, case_fit_dataflow,
    case_vocab_build, case_vocab_lookup, case_embedding_bag,
    case_embedding_bag_cached, case_embedding_bag_cache_only,
]
CASE_IDS = [c.__name__.removeprefix("case_") for c in CASES]


def _as_arrays(out):
    if isinstance(out, (tuple, list)):
        return [np.asarray(a) for a in out]
    if isinstance(out, dict):
        return [np.asarray(out[k]) for k in sorted(out)]
    return [np.asarray(out)]


# ---------------------------------------------------------------------------
# everywhere: capability, trace smokes, cross-form equality
# ---------------------------------------------------------------------------

def test_default_interpret_matches_backend_capability():
    """interpret defaults OFF exactly when a compiled Pallas target exists."""
    target = compiled_backend()
    if jax.default_backend() == "tpu":
        assert target == "mosaic"
    else:
        assert target is None
    assert default_interpret() is (target is None)


@pytest.mark.parametrize("case", CASES, ids=CASE_IDS)
def test_compiled_trace_smoke(case):
    """Every entry point traces with interpret=False on ANY host: Pallas
    binds the kernel jaxpr and validates block shapes at trace time, so a
    Mosaic-shape regression in the kernel body fails here, without TPUs."""
    fn, args = case(interpret=False)
    out = jax.eval_shape(fn, *args)
    assert jax.tree_util.tree_leaves(out)


def test_fit_build_forms_bit_identical():
    """The serialized fit build (one scalar read-modify-write per value,
    the only form, compiled and interpreted alike) == the reference
    scatter build (``ref.vocab_build_chunk`` + ``ref.vocab_counts_chunk``),
    bit for bit: min/add accumulation is order-independent, and negative /
    out-of-capacity values drop in both."""
    cap = 96
    vals = RNG.integers(-2, cap + 2, size=(203, 3)).astype(np.int32)
    for block_rows in (64, 256):
        fn = make_fit_dataflow([StreamInput("v", 3, np.dtype(np.int32))],
                               [], "v", cap, block_rows=block_rows,
                               interpret=True)
        fp, cnt = _as_arrays(fn(jnp.asarray(vals)))
        kept = np.where((vals >= 0) & (vals < cap), vals, cap)  # cap: drop
        ref_fp = ref.vocab_build_chunk(jnp.asarray(kept), cap + 1)[:cap]
        ref_cnt = ref.vocab_counts_chunk(jnp.asarray(kept.reshape(-1)),
                                         cap + 1)[:cap]
        np.testing.assert_array_equal(fp, np.asarray(ref_fp))
        np.testing.assert_array_equal(cnt, np.asarray(ref_cnt))


# ---------------------------------------------------------------------------
# everywhere: planner fallback + traced-count parity
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def _paper_modes():
    from repro.core.pipeline import paper_pipeline
    mk = lambda interp: paper_pipeline("II", small_vocab=512).compile(
        backend="pallas", interpret=interp)
    return mk(True), mk(False)


def test_compiled_mode_keeps_fusion_and_call_count(_paper_modes):
    """When every slice stays legal under the compiled budget, both modes
    lower the SAME plan: same paths, same traced pallas_call count."""
    from repro.data import synth
    pi, pc = _paper_modes
    assert pi.plan.compiled_mode is False and pc.plan.compiled_mode is True
    paths = lambda p: {k: v["path"] for k, v in p.lowering_report().items()}
    assert paths(pi) == paths(pc)
    raw = next(synth.dataset_batches("II", rows=200, batch_size=200, seed=9))
    assert pi.traced_pallas_call_count(raw) == pc.traced_pallas_call_count(raw)


def test_mosaic_illegal_fallback_never_crashes():
    """A slice legal under the logical budget but over the compiled one
    (lane padding + gather scratch, here of 1,024-row tiles) falls back
    staged with reason_kind "mosaic-illegal" — and only in compiled mode."""
    from repro.core.pipeline import paper_pipeline
    mk = lambda interp: paper_pipeline("II", small_vocab=1 << 20).compile(
        backend="pallas", interpret=interp, row_tile=1024)
    pi, pc = mk(True), mk(False)
    assert pi.lowering_report()["sparse"]["path"] == "grouped"
    rep = pc.lowering_report()["sparse"]
    assert rep["path"] == "staged"
    assert rep["reason_kind"] == "mosaic-illegal"
    # interpret-legal slices stay fused in compiled mode
    assert pc.lowering_report()["dense"]["path"] == "grouped"


def test_bench_refuses_cross_interpret_comparison():
    """The perf-trajectory compare hard-refuses to diff runs measured in
    different interpret modes (a lowering delta, not a regression)."""
    from benchmarks.bench_pipelines import compare_to_baseline
    rec = [dict(dataset="I", pipeline="I", variant="fused_vs_staged",
                speedup=8.0)]
    a = {"interpret": True, "records": rec}
    b = {"interpret": False, "records": rec}
    with pytest.raises(SystemExit, match="cross-interpret-mode"):
        compare_to_baseline(a, b)
    # same-mode: no regression at equal speedups, regression when degraded
    assert compare_to_baseline(a, dict(a)) == []
    worse = {"interpret": True,
             "records": [dict(rec[0], speedup=2.0)]}
    assert compare_to_baseline(worse, a)


# ---------------------------------------------------------------------------
# compiled target present: bit-exact parity
# ---------------------------------------------------------------------------

@needs_compiled
@pytest.mark.parametrize("case", CASES, ids=CASE_IDS)
def test_compiled_bit_identical_to_interpret(case):
    fn_i, args = case(interpret=True)
    fn_c, _ = case(interpret=False)  # same kernel, compiled; same inputs
    a = _as_arrays(fn_i(*args))
    b = _as_arrays(fn_c(*args))
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
