"""Lookahead embedding prefetch with a device-resident hot-row cache.

The ETL side is end-to-end streaming, so the dominant remaining hot path is
the trainer-side sparse embedding gather that consumes the ETL output — the
bottleneck BagPipe attacks with lookahead-driven caching and Hotline with a
popular/rare split (PAPERS.md).  The executor sees batches several steps
before ``jit_train_step`` does; at recommender scale the skewed hot set of
embedding rows is small, so peeking ahead, deduping indices, and keeping hot
rows in a device-resident cache converts most of the irregular HBM gather
into a dense cache lookup.

Three pieces, split host/device exactly like the rest of the runtime:

- ``LookaheadPlanner`` — pure host-side policy.  It maintains per-table row
  frequency over a window of W upcoming batches and, when the oldest batch
  is released, emits a ``PrefetchPlan``: a per-table index remap (hot row →
  cache slot, cold row → original id), the rows to stage for this batch, and
  a cache-update plan (admit/evict chosen by window frequency).  Everything
  is planned once on the host so device work stays dense.
- ``LookaheadStage`` — the executor stage (after **place**, before deliver).
  It buffers W in-flight envelopes, feeds the planner, and annotates each
  released payload with the plan arrays under ``PLAN_KEYS``.
- ``EmbedCache`` — the device-side consumer.  ``advance(tables, batch)``
  applies the batch's plan to the stacked ``[T, rows + stage_max, dim]``
  cache tensor (admits + per-batch staging, one dense scatter each) and
  returns kernel-ready inputs; ``cached_embedding_lookup`` is the
  differentiable wrapper over ``kernels.embedding_bag_cached`` (backward is
  the standard scatter-add to the table through the ORIGINAL row ids, so
  training gradients are exact).

Slot layout: slots ``[0, rows)`` are the resident hot set (persist across
batches, admit/evict managed by the planner), slots ``[rows, rows +
stage_max)`` are the per-batch staging region — cold rows of the released
batch prefetched just-in-time, the BagPipe "prefetch upcoming rows" move.
A cold row that overflows the staging region keeps ``slot == -1`` and falls
through ``embedding_bag_cached``'s partitioned table pass, so the remap is
total and bit-exact regardless of cache pressure.

Coherence: with a static table (ETL benches, serving) rows are copied on
admit only.  Under training the table changes every step, so
``EmbedCacheConfig(refresh=True)`` re-admits every *referenced* resident row
from the current table each batch — the HBM gather still touches only the
deduped unique rows (the win BagPipe measures) and cached training stays
bit-exact.  Vocab-state versions do not invalidate the cache: it is keyed on
post-VocabMap row ids of the trainer's table, not on raw values.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
from dataclasses import replace
from typing import Callable, Optional

import numpy as np
from jax.profiler import TraceAnnotation

# Keys the lookahead stage adds to each released payload (host numpy arrays).
PLAN_KEYS = ("emb_slot", "emb_cold", "emb_stage_rows",
             "emb_admit_slots", "emb_admit_rows")


@dataclasses.dataclass(frozen=True)
class EmbedCacheConfig:
    """Knobs for the lookahead prefetch + embedding cache layer.

    rows : resident cache slots per table (the device hot set).
    window : lookahead window W in batches; frequency (and therefore the
        hot set) is computed over the W in-flight envelopes.
    stage_max : per-batch staging slots appended after the resident region
        (0 -> ``rows``).  Cold rows beyond this fall through the kernel's
        partitioned table pass.
    tables : feature columns of the index matrix that get a cache (per-table
        on/off); None = every column.
    key : payload key holding the int32 ``[B, F]`` index matrix.
    min_admit_freq : window occurrences before a row may displace a resident.
    refresh : re-admit referenced resident rows from the current table every
        batch (exactness under training updates; leave False for static
        tables).
    row_bytes : bytes per embedding row, for gather-bytes-saved accounting.
    """

    rows: int
    window: int = 4
    stage_max: int = 0
    tables: Optional[tuple] = None
    key: str = "sparse"
    min_admit_freq: int = 2
    refresh: bool = False
    row_bytes: int = 0

    def stage_slots(self) -> int:
        return self.stage_max if self.stage_max > 0 else self.rows

    def admit_slots(self) -> int:
        # admits are bounded by the cache size; refresh adds at most one
        # entry per resident slot on top
        return self.rows * (2 if self.refresh else 1)


@dataclasses.dataclass
class CacheStats:
    """Lookahead/cache accounting (exported by ``etl_runtime.metrics``)."""

    lookups: int = 0        # index entries planned (excl. -1 padding)
    hits: int = 0           # served by a row already resident before the plan
    misses: int = 0         # lookups whose row was not resident
    admitted: int = 0       # rows copied table -> resident slots (incl. refresh)
    evicted: int = 0        # resident rows displaced by admission
    staged: int = 0         # unique cold rows staged per batch
    overflow_cold: int = 0  # lookups left to the partitioned fall-through
    row_bytes: int = 0

    def hit_rate(self) -> float:
        return self.hits / self.lookups if self.lookups else 0.0

    def gather_bytes_saved(self) -> float:
        """HBM gather traffic avoided vs the uncached kernel: every lookup
        would have been one table-row fetch; the cached path fetches only
        admitted + staged + fall-through rows."""
        fetched = self.admitted + self.staged + self.overflow_cold
        return max(0, self.lookups - fetched) * self.row_bytes

    def as_dict(self) -> dict:
        return {"lookups": self.lookups, "hits": self.hits,
                "misses": self.misses, "admitted": self.admitted,
                "evicted": self.evicted, "staged": self.staged,
                "overflow_cold": self.overflow_cold,
                "hit_rate": self.hit_rate(),
                "gather_bytes_saved": self.gather_bytes_saved()}


@dataclasses.dataclass
class PrefetchPlan:
    """Per-batch annotation, all host numpy, shapes static per config:

    slot  : int32[B, T]  ext-cache slot per lookup (-1 = fall through)
    cold  : int32[B, T]  original row where slot == -1 (-1 = padding lane)
    stage_rows  : int32[T, E]  rows staged into slots [rows, rows+E) (-1 pad)
    admit_slots : int32[T, A]  resident slots to overwrite before the batch
    admit_rows  : int32[T, A]  table rows to copy into those slots (-1 pad)
    """

    slot: np.ndarray
    cold: np.ndarray
    stage_rows: np.ndarray
    admit_slots: np.ndarray
    admit_rows: np.ndarray

    def as_payload(self) -> dict:
        return dict(zip(PLAN_KEYS, (self.slot, self.cold, self.stage_rows,
                                    self.admit_slots, self.admit_rows)))


class LookaheadPlanner:
    """Host-side window frequency + hot set + remap planner.

    Drive it with ``push(idx)`` as batches enter the window and
    ``pop_plan(idx)`` as the oldest batch is released (idx is that batch's
    int32 ``[B, T]`` column-selected index matrix).  The plan for a batch is
    computed while the batch itself and its W-1 successors are in the window.
    """

    def __init__(self, cfg: EmbedCacheConfig, n_tables: int,
                 stats: Optional[CacheStats] = None):
        self.cfg = cfg
        self.n_tables = n_tables
        self.stats = stats if stats is not None \
            else CacheStats(row_bytes=cfg.row_bytes)
        self._window: collections.deque = collections.deque()
        self._freq = [collections.Counter() for _ in range(n_tables)]
        self._slot_of = [dict() for _ in range(n_tables)]   # row -> slot
        self._row_of = [np.full(cfg.rows, -1, np.int64)
                        for _ in range(n_tables)]           # slot -> row
        self._free = [list(range(cfg.rows - 1, -1, -1))
                      for _ in range(n_tables)]

    # -- window maintenance ------------------------------------------------

    def push(self, idx: np.ndarray) -> None:
        """A batch entered the window: count its rows (padding -1 ignored)."""
        idx = np.asarray(idx)
        self._window.append(idx)
        for t in range(self.n_tables):
            col = idx[:, t]
            u, c = np.unique(col[col >= 0], return_counts=True)
            self._freq[t].update(dict(zip(u.tolist(), c.tolist())))

    def window_depth(self) -> int:
        return len(self._window)

    def resident_rows(self, t: int) -> np.ndarray:
        return self._row_of[t][self._row_of[t] >= 0]

    # -- planning ----------------------------------------------------------

    def pop_plan(self) -> tuple[np.ndarray, PrefetchPlan]:
        """Release the oldest window batch: plan it, retire its counts."""
        if not self._window:
            raise ValueError("pop_plan on an empty window")
        idx = self._window[0]
        plan = self._plan(idx)
        self._retire(self._window.popleft())
        return idx, plan

    def _retire(self, idx: np.ndarray) -> None:
        for t in range(self.n_tables):
            col = idx[:, t]
            u, c = np.unique(col[col >= 0], return_counts=True)
            freq = self._freq[t]
            freq.subtract(dict(zip(u.tolist(), c.tolist())))
            for r in u.tolist():
                if freq[r] <= 0:
                    del freq[r]

    def _plan(self, idx: np.ndarray) -> PrefetchPlan:
        cfg = self.cfg
        B, T = idx.shape
        E, A = cfg.stage_slots(), cfg.admit_slots()
        slot = np.full((B, T), -1, np.int32)
        cold = np.full((B, T), -1, np.int32)
        stage_rows = np.full((T, E), -1, np.int32)
        admit_slots = np.full((T, A), -1, np.int32)
        admit_rows = np.full((T, A), -1, np.int32)
        for t in range(self.n_tables):
            self._plan_table(t, idx[:, t], slot[:, t], cold[:, t],
                             stage_rows[t], admit_slots[t], admit_rows[t])
        return PrefetchPlan(slot, cold, stage_rows, admit_slots, admit_rows)

    def _plan_table(self, t: int, col, slot_out, cold_out, stage_out,
                    admit_slot_out, admit_row_out) -> None:
        cfg, st = self.cfg, self.stats
        freq, slot_of, row_of = self._freq[t], self._slot_of[t], self._row_of[t]
        valid = col >= 0
        u, inv = np.unique(col[valid], return_inverse=True)
        resident_before = np.fromiter(
            (slot_of.get(int(r), -1) for r in u), np.int32, len(u))

        # admission: window-frequent rows displace the coldest residents
        desired = [r for r, c in freq.most_common(cfg.rows)
                   if c >= cfg.min_admit_freq]
        admits = [r for r in desired if r not in slot_of]
        n_admit = 0
        if admits:
            desired_set = set(desired)
            victims = sorted((r for r in row_of[row_of >= 0].tolist()
                              if r not in desired_set),
                             key=lambda r: freq[r] if r in freq else 0)
            for row in admits:
                if self._free[t]:
                    s = self._free[t].pop()
                elif victims:
                    old = victims.pop(0)
                    s = slot_of.pop(old)
                    st.evicted += 1
                else:
                    break  # cache full of desired rows: stop admitting
                slot_of[row] = s
                row_of[s] = row
                admit_slot_out[n_admit] = s
                admit_row_out[n_admit] = row
                n_admit += 1
        st.admitted += n_admit

        # remap against the post-admission resident set
        resident_after = np.fromiter(
            (slot_of.get(int(r), -1) for r in u), np.int32, len(u))
        hit_u = (resident_before >= 0) & (resident_after >= 0)
        counts = np.bincount(inv, minlength=len(u))
        st.lookups += int(valid.sum())
        st.hits += int(counts[hit_u].sum())
        st.misses += int(valid.sum()) - int(counts[hit_u].sum())

        # stage this batch's cold rows just-in-time (dedup'd); overflow
        # falls through the kernel's partitioned pass
        cold_u = np.flatnonzero(resident_after < 0)
        staged_u = cold_u[: len(stage_out)]
        stage_out[: len(staged_u)] = u[staged_u]
        ext_slot = resident_after.copy()
        ext_slot[staged_u] = cfg.rows + np.arange(len(staged_u), dtype=np.int32)
        st.staged += len(staged_u)
        overflow_u = np.zeros(len(u), bool)
        overflow_u[cold_u[len(stage_out):]] = True
        st.overflow_cold += int(counts[overflow_u].sum())

        if cfg.refresh:
            # exactness under training: re-copy every referenced resident
            # row from the current table (HBM still touched once per unique
            # row — the dedup win — never once per lookup)
            ref_u = np.flatnonzero(hit_u)
            n_ref = min(len(ref_u), len(admit_slot_out) - n_admit)
            admit_slot_out[n_admit:n_admit + n_ref] = resident_after[ref_u[:n_ref]]
            admit_row_out[n_admit:n_admit + n_ref] = u[ref_u[:n_ref]]
            st.admitted += n_ref

        slot_out[valid] = ext_slot[inv]
        cold_full = np.where(ext_slot < 0, u, -1).astype(np.int32)
        cold_out[valid] = cold_full[inv]


class LookaheadStage(threading.Thread):
    """Executor stage: window W envelopes after place, annotate with plans.

    Mirrors ``_SortStage``'s shape: bounded buffering, EOS drains the
    partial window, stop aborts promptly, errors surface via ``on_error``.
    Reading the index matrix synchronizes that payload's device future —
    acceptable here because the stage sits behind the transform dispatch and
    its host work is the point (plans ride the envelope, device work at the
    consumer stays dense).
    """

    def __init__(self, stats, in_q, out_q, cfg: EmbedCacheConfig, *,
                 cache_stats: Optional[CacheStats] = None,
                 drop_oldest: bool = False,
                 on_put: Optional[Callable[[int], None]] = None,
                 on_error: Optional[Callable[[BaseException], None]] = None,
                 clock=None):
        super().__init__(name=f"etl-{stats.name}", daemon=True)
        from repro.etl_runtime.clock import SYSTEM_CLOCK
        self.stats = stats
        self.in_q = in_q
        self.out_q = out_q
        self.cfg = cfg
        self.cache_stats = cache_stats
        # the planner is built on the first batch: with cfg.tables=None the
        # index-matrix width is only known once a payload arrives
        self.planner: Optional[LookaheadPlanner] = None
        self.drop_oldest = drop_oldest
        self.on_put = on_put
        self.on_error = on_error
        self._clock = clock or SYSTEM_CLOCK
        self._buf: collections.deque = collections.deque()
        # live window knob (the controller's lookahead_window actuator);
        # frequency counts always cover the in-flight buffer whatever the
        # current target, so shrinking mid-run just drains the excess
        self._window = max(1, cfg.window)

    def set_window(self, window: int) -> None:
        """Retarget the lookahead depth W; takes effect on the next batch
        (a shrink releases the now-excess envelopes then)."""
        self._window = max(1, int(window))

    def _indices(self, payload) -> np.ndarray:
        idx = np.asarray(payload[self.cfg.key])
        if idx.ndim != 2:
            raise ValueError(
                f"lookahead key {self.cfg.key!r} must be a [batch, tables] "
                f"index matrix, got shape {idx.shape}")
        if self.cfg.tables is not None:
            idx = idx[:, list(self.cfg.tables)]
        return idx.astype(np.int64, copy=False)

    def _release(self) -> bool:
        env = self._buf.popleft()
        _, plan = self.planner.pop_plan()
        payload = dict(env.payload)
        payload.update(plan.as_payload())
        mono = self._clock.monotonic
        t0 = mono()
        r = self.out_q.put(replace(env, payload=payload),
                           drop_oldest=self.drop_oldest)
        self.stats.wait_out_s += mono() - t0
        from repro.etl_runtime.runtime import _STOPPED
        if r is _STOPPED:
            return False
        self.stats.items += 1
        self.stats.drop_oldest += r
        if self.on_put:
            self.on_put(r)
        return True

    def run(self):
        from repro.etl_runtime.runtime import _EOS, _STOPPED
        mono = self._clock.monotonic
        while True:
            t0 = mono()
            item = self.in_q.get()
            self.stats.wait_in_s += mono() - t0
            if item is _STOPPED:
                return
            if item is _EOS:
                while self._buf:
                    t1 = mono()
                    ok = self._release()
                    self.stats.busy_s += mono() - t1
                    if not ok:
                        return
                self.out_q.put(_EOS)
                return
            t1 = mono()
            try:
                with TraceAnnotation("etl.lookahead", batch=item.batch):
                    idx = self._indices(item.payload)
                    if self.planner is None:
                        self.planner = LookaheadPlanner(
                            self.cfg, idx.shape[1], stats=self.cache_stats)
                    self.planner.push(idx)
                    self._buf.append(item)
                    ok = True
                    # drain to the live window target (shrunk knobs release
                    # the excess; at steady state this pops one per push)
                    while ok and len(self._buf) >= self._window:
                        ok = self._release()
            except Exception as e:
                if self.on_error:
                    self.on_error(e)
                return
            self.stats.busy_s += mono() - t1
            if not ok:
                return


# ---------------------------------------------------------------------------
# device side: cache tensor lifecycle + differentiable cached lookup
# ---------------------------------------------------------------------------

class EmbedCache:
    """Device-resident stacked cache ``[T, rows + stage_max, dim]`` plus the
    per-batch ``advance`` that consumes ``PLAN_KEYS`` annotations.

    ``advance(tables, batch)`` pops the plan arrays from the payload dict,
    applies the admit plan and the per-batch staging from the CURRENT
    ``tables`` (``[T, vocab, dim]``) with two dense vmapped scatters (planned
    once on the host, so the device work has static shapes), and returns the
    batch with ``emb_cache`` / ``emb_slot`` / ``emb_cold`` kernel inputs.
    Batches carrying plans must be advanced in delivery order — the planner's
    host mirror assumes every admit executes.
    """

    def __init__(self, cfg: EmbedCacheConfig, n_tables: int, dim: int,
                 dtype=np.float32):
        import jax
        import jax.numpy as jnp

        self.cfg = cfg
        self.n_tables = n_tables
        self.dim = dim
        rows, stage = cfg.rows, cfg.stage_slots()
        self.ext = jnp.zeros((n_tables, rows + stage, dim), dtype)
        self.generation = 0  # bumped by invalidate() on state-version swaps

        def _apply(ext, tables, admit_slots, admit_rows, stage_rows):
            ce = rows + stage
            gather = jax.vmap(lambda tb, r: tb[jnp.clip(r, 0)])
            adm_vals = gather(tables, admit_rows)
            safe_slots = jnp.where(admit_slots < 0, ce, admit_slots)
            ext = jax.vmap(
                lambda c, s, v: c.at[s].set(v, mode="drop"))(
                    ext, safe_slots, adm_vals)
            stage_vals = gather(tables, stage_rows)
            return ext.at[:, rows:, :].set(stage_vals)

        self._apply = jax.jit(_apply, donate_argnums=(0,))

    def invalidate(self) -> None:
        """Zero every cache row on a vocabulary state-version swap.

        An incremental refit (``CompiledPipeline.fit_incremental``) keeps
        existing value→rank assignments, so the planner's slot→row mapping
        stays valid across the swap — but cached row *contents* may belong
        to the pre-swap embedding landscape, so the trainer drops them all.
        Requires ``cfg.refresh=True`` to be bit-exact afterwards: refresh
        re-admits every referenced resident from the current tables before
        its next use, so no lookup ever reads an invalidated (zeroed) row.
        ``generation`` counts swaps for observability.
        """
        import jax.numpy as jnp

        self.ext = jnp.zeros_like(self.ext)
        self.generation += 1

    def advance(self, tables, batch: dict) -> dict:
        import jax.numpy as jnp

        if PLAN_KEYS[0] not in batch:
            return batch  # un-planned batch (e.g. warmup before the window)
        batch = dict(batch)
        slot, cold, stage_rows, admit_slots, admit_rows = (
            batch.pop(k) for k in PLAN_KEYS)
        self.ext = self._apply(self.ext, tables,
                               jnp.asarray(admit_slots),
                               jnp.asarray(admit_rows),
                               jnp.asarray(stage_rows))
        batch["emb_cache"] = self.ext
        batch["emb_slot"] = jnp.asarray(slot)
        batch["emb_cold"] = jnp.asarray(cold)
        return batch


def cached_embedding_lookup(tables, cache, slot, cold, orig, *,
                            partitions: int = 1,
                            interpret: "bool | None" = None):
    """Differentiable per-feature cached lookup: ``(B, T)`` single-hot
    indices against stacked ``tables [T, V, d]`` and ``cache [T, C, d]``,
    returning ``(B, T, d)``.

    Forward resolves each feature through ``kernels.embedding_bag_cached``
    (hot slots from the cache tile, cold rows through the partitioned table
    pass).  Backward scatter-adds the cotangent into the TABLE at the
    original row ids ``orig`` — the exact uncached gradient — and sends a
    zero to the cache (its rows mirror table rows, so all sensitivity
    belongs to the table).
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels import embedding_bag as bag

    n_tables = tables.shape[0]
    vocab = tables.shape[1]

    @jax.custom_vjp
    def lookup(tables, cache):
        outs = [bag.embedding_bag_cached(
            tables[t], cache[t], slot[:, t:t + 1], cold[:, t:t + 1],
            partitions=partitions, interpret=interpret)
            for t in range(n_tables)]
        return jnp.stack(outs, axis=1)  # (B, T, d)

    def fwd(tables, cache):
        return lookup(tables, cache), ()

    def bwd(_, g):  # g: (B, T, d)
        safe = jnp.where(orig < 0, vocab, orig)  # -1 lanes drop
        d_tables = jax.vmap(
            lambda o, gt: jnp.zeros(tables.shape[1:], g.dtype)
            .at[o].add(gt, mode="drop"))(safe.T, g.transpose(1, 0, 2))
        return d_tables.astype(tables.dtype), jnp.zeros_like(cache)

    lookup.defvjp(fwd, bwd)
    return lookup(tables, cache)
