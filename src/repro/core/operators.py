"""Software-defined ETL operators (paper Table 1) with fit/apply semantics.

Each operator provides three things:

1. ``numpy(x)``   — the pure-numpy oracle (the "CPU pandas baseline" semantics);
2. ``jnp_expr(x)``— a jax.numpy expression implementing the identical transform.
   The expression is written so it is valid BOTH under ``jax.jit`` and inside a
   Pallas kernel body; the compiler chains these expressions to code-generate a
   fused streaming stage (PipeRec's operator fusion, §3.1 step 2).
3. planner metadata — category (dense/sparse/both), statefulness, fusability,
   per-element cost estimates and state size (for the BRAM-vs-HBM analogue
   VMEM-vs-HBM placement decision).

Stateful operators (VocabGen/VocabMap) additionally expose a streaming ``fit``
protocol: ``init_state() -> update(state, batch, row_offset) -> finalize``.
The fit phase is the paper's keyed reduction that builds the vocabulary table;
the apply phase consumes the frozen table (point-in-time correctness: tables are
versioned and frozen before any batch that uses them is emitted).
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

# Sentinel used for "missing" in integer columns (dense columns use NaN).
INT_MISSING = np.int32(-(2 ** 31))

DENSE, SPARSE, BOTH = "dense", "sparse", "both"


def _mix32_np(x: np.ndarray) -> np.ndarray:
    """lowbias32 finalizer (32-bit splitmix analogue). uint32 -> uint32.

    TPU adaptation note: Pallas/TPU has no 64-bit integers, so SigridHash's
    64-bit hash is replaced by this 32-bit double-round multiplicative mix.
    """
    x = x.astype(np.uint32)
    x ^= x >> 16
    x = (x * np.uint32(0x7FEB352D)).astype(np.uint32)
    x ^= x >> 15
    x = (x * np.uint32(0x846CA68B)).astype(np.uint32)
    x ^= x >> 16
    return x


def _mix32_jnp(x):
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


# Cephes ``logf`` minimax coefficients for log(1 + f), f in [sqrt(.5)-1,
# sqrt(2)-1): log(1 + f) = f - f**2/2 + f**3 * P(f)
_LOG_P = (7.0376836292e-2, -1.1514610310e-1, 1.1676998740e-1,
          -1.2420140846e-1, 1.4249322787e-1, -1.6668057665e-1,
          2.0000714765e-1, -2.4999993993e-1, 3.3333331174e-1)


def _pow2_f32(e):
    """2.0**e as float32 for int32 ``e``, clamped to the normal range."""
    return jax.lax.bitcast_convert_type(
        jnp.clip(e + 127, 1, 254) << 23, jnp.float32)


def log1p_f32(x):
    """float32 log(1 + x) within 1 ulp of the correctly rounded value, from
    adds, multiplies and bit operations only.

    The TPU's own ``log1p`` (XLA and Mosaic alike) is about 2.6e-4 off
    numpy's in relative terms; this form is accurate on every backend.
    1 + x = m * 2**k with m in [sqrt(.5), sqrt(2)) is split off the exponent
    bits, log(m) comes from Cephes' polynomial, and the rounding error of
    1 + x is added back as c / (1 + x).  ``m * 2**k - 1`` rebuilds u - 1
    from the split so that no simplifier folds ``(1 + x) - 1`` to ``x``.
    """
    u = 1.0 + x
    bits = jax.lax.bitcast_convert_type(u, jnp.int32)
    k = (bits - 0x3F3504F3) >> 23
    m = jax.lax.bitcast_convert_type(bits - (k << 23), jnp.float32)
    f = m - 1.0
    z = f * f
    # rounding error of u (exact below 2**24; negligible beyond)
    c = jnp.where(k < 25, x - (m * _pow2_f32(k) - 1.0), 0.0)
    corr = (c * _pow2_f32(-k)) * (1.0 - f + z)  # ~ c / u
    p = jnp.float32(_LOG_P[0])
    for coef in _LOG_P[1:]:
        p = p * f + jnp.float32(coef)
    kf = k.astype(jnp.float32)
    y = f * z * p + kf * jnp.float32(-2.12194440e-4) - 0.5 * z + corr
    r = (f + y) + kf * jnp.float32(0.693359375)
    r = jnp.where(u == jnp.inf, jnp.inf, r)
    r = jnp.where(u == 0.0, -jnp.inf, r)
    return jnp.where((u < 0.0) | (x != x), jnp.nan, r)


@dataclasses.dataclass
class Operator:
    """Base class. Subclasses are cheap, declarative dataclasses."""

    # planner metadata (overridden per subclass)
    category: str = dataclasses.field(default=BOTH, init=False)
    stateful: bool = dataclasses.field(default=False, init=False)
    # fusable: elementwise + shape-preserving -> can join a fused stage
    fusable: bool = dataclasses.field(default=True, init=False)
    flops_per_elem: float = dataclasses.field(default=1.0, init=False)

    @property
    def name(self) -> str:
        return type(self).__name__

    # dtype of the output column block given input dtype
    def out_dtype(self, in_dtype: np.dtype) -> np.dtype:
        return np.dtype(in_dtype)

    # width multiplier (OneHot expands a column into K columns)
    def width_factor(self) -> int:
        return 1

    def numpy(self, x: np.ndarray) -> np.ndarray:
        raise NotImplementedError

    def jnp_expr(self, x):
        raise NotImplementedError

    def validate(self, in_dtype: np.dtype) -> None:
        """Type/shape constraint check (planner step 1)."""
        del in_dtype

    def state_bytes(self) -> int:
        return 0


# --------------------------------------------------------------------------
# Dense stateless operators
# --------------------------------------------------------------------------


@dataclasses.dataclass
class Clamp(Operator):
    """Restrict values to [lo, hi]; paper default clips negatives to zero."""

    lo: float = 0.0
    hi: float = float("inf")

    def __post_init__(self):
        self.category = DENSE

    def numpy(self, x):
        return np.clip(x, self.lo, None if np.isinf(self.hi) else self.hi)

    def jnp_expr(self, x):
        y = jnp.maximum(x, jnp.asarray(self.lo, x.dtype))
        if not np.isinf(self.hi):
            y = jnp.minimum(y, jnp.asarray(self.hi, x.dtype))
        return y

    def validate(self, in_dtype):
        if not np.issubdtype(in_dtype, np.floating):
            raise TypeError(f"Clamp expects float input, got {in_dtype}")


@dataclasses.dataclass
class Logarithm(Operator):
    """log(x + 1): reduces skew / compresses heavy tails."""

    def __post_init__(self):
        self.category = DENSE
        self.flops_per_elem = 10.0  # transcendental

    def numpy(self, x):
        return np.log1p(x)

    def jnp_expr(self, x):
        return log1p_f32(x.astype(jnp.float32)).astype(x.dtype)

    def validate(self, in_dtype):
        if not np.issubdtype(in_dtype, np.floating):
            raise TypeError(f"Logarithm expects float input, got {in_dtype}")


@dataclasses.dataclass
class FillMissing(Operator):
    """Impute NaNs (float) or INT_MISSING sentinels (int) with a default."""

    default: float = 0.0

    def numpy(self, x):
        if np.issubdtype(x.dtype, np.floating):
            return np.where(np.isnan(x), np.asarray(self.default, x.dtype), x)
        return np.where(x == INT_MISSING, np.asarray(int(self.default), x.dtype), x)

    def jnp_expr(self, x):
        if jnp.issubdtype(x.dtype, jnp.floating):
            return jnp.where(jnp.isnan(x), jnp.asarray(self.default, x.dtype), x)
        return jnp.where(x == INT_MISSING, jnp.asarray(int(self.default), x.dtype), x)


@dataclasses.dataclass
class Bucketize(Operator):
    """Discretize a scalar by bin boundaries: x=37, bins=[10,20,40] -> 3.

    Implemented as sum(x >= b_i) with compile-time constant boundaries, which
    fuses into the streaming stage (searchsorted would break elementwise fusion).
    """

    boundaries: Sequence[float] = ()

    def __post_init__(self):
        self.boundaries = tuple(float(b) for b in self.boundaries)
        if list(self.boundaries) != sorted(self.boundaries):
            raise ValueError("Bucketize boundaries must be sorted")
        self.flops_per_elem = float(len(self.boundaries))

    def out_dtype(self, in_dtype):
        return np.dtype(np.int32)

    def numpy(self, x):
        out = np.zeros(x.shape, np.int32)
        for b in self.boundaries:
            out += (x >= b).astype(np.int32)
        return out

    def jnp_expr(self, x):
        out = jnp.zeros(x.shape, jnp.int32)
        for b in self.boundaries:
            out = out + (x >= jnp.asarray(b, x.dtype)).astype(jnp.int32)
        return out


@dataclasses.dataclass
class OneHot(Operator):
    """Encode small-cardinality bins as K-wide indicators (expands width)."""

    depth: int = 2

    def __post_init__(self):
        self.fusable = False  # expands the column axis
        self.flops_per_elem = float(self.depth)

    def width_factor(self) -> int:
        return self.depth

    def out_dtype(self, in_dtype):
        return np.dtype(np.float32)

    def numpy(self, x):
        x = x.astype(np.int64)
        eye = np.eye(self.depth, dtype=np.float32)
        flat = np.clip(x, 0, self.depth - 1).reshape(-1)
        out = eye[flat].reshape(x.shape + (self.depth,))
        # out-of-range -> all-zero row (match jax.nn.one_hot semantics)
        mask = ((x >= 0) & (x < self.depth)).astype(np.float32)[..., None]
        out = out * mask
        return out.reshape(x.shape[:-1] + (x.shape[-1] * self.depth,))

    def jnp_expr(self, x):
        k = jnp.arange(self.depth, dtype=x.dtype)
        out = (x[..., None] == k).astype(jnp.float32)
        return out.reshape(x.shape[:-1] + (x.shape[-1] * self.depth,))

    def validate(self, in_dtype):
        if not np.issubdtype(in_dtype, np.integer):
            raise TypeError(f"OneHot expects integer input, got {in_dtype}")


# --------------------------------------------------------------------------
# Sparse stateless operators
# --------------------------------------------------------------------------


@dataclasses.dataclass
class Hex2Int(Operator):
    """Fixed-width ASCII-hex column -> int32 (two's complement on overflow).

    Input block has a trailing hex-digit axis: uint8[rows, cols, width].
    Missing values are encoded as all-0x00 strings and map to INT_MISSING.
    """

    width: int = 8

    def __post_init__(self):
        self.category = SPARSE
        self.flops_per_elem = 4.0 * self.width

    def out_dtype(self, in_dtype):
        return np.dtype(np.int32)

    @staticmethod
    def _digit_np(c: np.ndarray) -> np.ndarray:
        c = c.astype(np.int64)
        return np.where(c >= 97, c - 87, np.where(c >= 65, c - 55, c - 48))

    def numpy(self, x):
        assert x.shape[-1] == self.width and x.dtype == np.uint8
        missing = np.all(x == 0, axis=-1)
        dig = self._digit_np(np.where(x == 0, np.uint8(48), x))
        val = np.zeros(x.shape[:-1], np.uint64)
        for i in range(self.width):
            val = (val << np.uint64(4)) | dig[..., i].astype(np.uint64)
        out = (val & np.uint64(0xFFFFFFFF)).astype(np.uint32).view(np.int32)
        return np.where(missing, INT_MISSING, out)

    def jnp_expr(self, x):
        missing = jnp.all(x == 0, axis=-1)
        c = jnp.where(x == 0, jnp.uint8(48), x).astype(jnp.int32)
        dig = jnp.where(c >= 97, c - 87, jnp.where(c >= 65, c - 55, c - 48))
        dig = dig.astype(jnp.uint32)
        val = jnp.zeros(x.shape[:-1], jnp.uint32)
        for i in range(self.width):
            val = (val << jnp.uint32(4)) | dig[..., i]
        out = val.astype(jnp.int32)
        return jnp.where(missing, INT_MISSING, out)

    def validate(self, in_dtype):
        if np.dtype(in_dtype) != np.uint8:
            raise TypeError(f"Hex2Int expects uint8 ASCII input, got {in_dtype}")


@dataclasses.dataclass
class Modulus(Operator):
    """Positive modulus: (-7) mod 5 -> 3. Bounds ids to [0, m)."""

    m: int = 65536

    def __post_init__(self):
        self.category = SPARSE
        if self.m <= 0:
            raise ValueError("Modulus m must be positive")

    def numpy(self, x):
        out = np.mod(x.astype(np.int64), self.m).astype(np.int32)
        return out

    def jnp_expr(self, x):
        # int32-safe positive mod (jnp.mod on int32 already follows sign of
        # divisor, but INT_MISSING edge cases go through the same path).
        return jnp.mod(x, jnp.asarray(self.m, x.dtype)).astype(jnp.int32)

    def validate(self, in_dtype):
        if not np.issubdtype(in_dtype, np.integer):
            raise TypeError(f"Modulus expects integer input, got {in_dtype}")


@dataclasses.dataclass
class SigridHash(Operator):
    """Bound categorical ids: hash(id) % m (32-bit mix; see DESIGN.md note)."""

    m: int = 65536

    def __post_init__(self):
        self.category = SPARSE
        self.flops_per_elem = 12.0

    def numpy(self, x):
        h = _mix32_np(x.astype(np.int64).astype(np.uint32) if x.dtype != np.uint32 else x)
        return np.mod(h, np.uint32(self.m)).astype(np.int32)

    def jnp_expr(self, x):
        h = _mix32_jnp(x)
        return jnp.mod(h, jnp.uint32(self.m)).astype(jnp.int32)

    def validate(self, in_dtype):
        if not np.issubdtype(in_dtype, np.integer):
            raise TypeError(f"SigridHash expects integer input, got {in_dtype}")


@dataclasses.dataclass
class Cartesian(Operator):
    """Cross two categorical columns into a new bounded key.

    Binary operator: planner wires two parents; jnp_expr2/numpy2 take both.
    """

    m: int = 65536

    def __post_init__(self):
        self.category = SPARSE
        self.fusable = False  # binary: joins two streams (broadcast edge)
        self.flops_per_elem = 16.0

    GOLDEN = 0x9E3779B1

    def numpy2(self, a, b):
        ha = _mix32_np(a.astype(np.int64).astype(np.uint32))
        hb = _mix32_np(b.astype(np.int64).astype(np.uint32))
        h = _mix32_np(ha ^ (hb * np.uint32(self.GOLDEN)).astype(np.uint32))
        return np.mod(h, np.uint32(self.m)).astype(np.int32)

    def jnp_expr2(self, a, b):
        ha = _mix32_jnp(a)
        hb = _mix32_jnp(b)
        h = _mix32_jnp(ha ^ (hb * jnp.uint32(self.GOLDEN)))
        return jnp.mod(h, jnp.uint32(self.m)).astype(jnp.int32)

    def numpy(self, x):  # pragma: no cover - binary op uses numpy2
        raise TypeError("Cartesian is a binary operator; use numpy2(a, b)")

    def jnp_expr(self, x):  # pragma: no cover
        raise TypeError("Cartesian is a binary operator; use jnp_expr2(a, b)")


# --------------------------------------------------------------------------
# Stateful vocabulary operators
# --------------------------------------------------------------------------

_POS_INF = np.int64(2 ** 62)


@dataclasses.dataclass
class VocabGen(Operator):
    """Build a value -> first-appearance-rank table over a bounded key space.

    Fit phase (paper: keyed reduction across the stream):
      first_pos[v] = min global position at which value v occurs;
      counts[v]    = number of occurrences (paper §3.2.2: the table "enables
                     further operations like frequency-based filtering").
    Finalize: values with counts >= min_count ranked by first_pos;
    table[v] = rank, filtered/absent = -1 (they map to OOV at apply time).

    The table has ``capacity`` slots (the range of the upstream Modulus).  The
    planner places it in VMEM when small, HBM when large (BRAM/HBM analogue).
    """

    capacity: int = 65536
    min_count: int = 1  # frequency filter threshold (1 = keep everything)

    def __post_init__(self):
        self.category = SPARSE
        self.stateful = True
        self.fusable = False

    def state_bytes(self) -> int:
        return 16 * self.capacity  # int64 first_pos + int64 counts during fit

    def table_bytes(self) -> int:
        return 4 * self.capacity  # frozen int32 table

    # ---- streaming fit protocol (numpy oracle) ----
    def init_state(self):
        return (np.full(self.capacity, _POS_INF, np.int64),
                np.zeros(self.capacity, np.int64))

    def update(self, state, x: np.ndarray, row_offset: int):
        first_pos, counts = state
        flat = x.reshape(-1).astype(np.int64)
        if flat.size and (flat.min() < 0 or flat.max() >= self.capacity):
            raise ValueError("VocabGen input out of [0, capacity) — add Modulus first")
        pos = row_offset + np.arange(flat.size, dtype=np.int64)
        np.minimum.at(first_pos, flat, pos)
        np.add.at(counts, flat, 1)
        return first_pos, counts

    def finalize(self, state) -> np.ndarray:
        """(first_pos, counts) -> rank table (int32, -1 = absent/filtered)."""
        first_pos, counts = state
        present = first_pos < _POS_INF
        if self.min_count > 1:
            present = present & (counts >= self.min_count)
        keyed = np.where(present, first_pos, _POS_INF)
        order = np.argsort(keyed, kind="stable")
        rank = np.empty(self.capacity, np.int64)
        rank[order] = np.arange(self.capacity)
        table = np.where(present, rank, -1).astype(np.int32)
        return table

    @staticmethod
    def n_unique(table: np.ndarray) -> int:
        return int((table >= 0).sum())

    # (the compiled jnp/pallas fit path lives in kernels/ref.py +
    #  kernels/vocab.py: chunked build -> int32x2 merge -> finalize)

    def numpy(self, x):  # identity in the apply phase (table already built)
        return x

    def jnp_expr(self, x):
        return x


@dataclasses.dataclass
class VocabMap(Operator):
    """Map values through a frozen vocabulary table; unseen -> OOV index.

    The OOV index equals n_unique (one past the last assigned rank), so the
    embedding table downstream needs n_unique + 1 rows.
    """

    capacity: int = 65536

    def __post_init__(self):
        self.category = SPARSE
        self.stateful = True  # consumes state produced by VocabGen
        self.fusable = False  # gather from a shared table (broadcast fabric)
        self.flops_per_elem = 2.0

    def state_bytes(self) -> int:
        return 4 * self.capacity

    def numpy_apply(self, x: np.ndarray, table: np.ndarray) -> np.ndarray:
        n_unique = VocabGen.n_unique(table)
        hit = table[x.astype(np.int64)]
        return np.where(hit >= 0, hit, n_unique).astype(np.int32)

    def jnp_apply(self, x, table, n_unique):
        hit = table[x]
        return jnp.where(hit >= 0, hit, n_unique).astype(jnp.int32)

    def numpy(self, x):  # pragma: no cover
        raise TypeError("VocabMap requires a table; use numpy_apply(x, table)")

    def jnp_expr(self, x):  # pragma: no cover
        raise TypeError("VocabMap requires a table; use jnp_apply(x, table, n)")


ALL_OPERATORS = [Clamp, Logarithm, FillMissing, Bucketize, OneHot,
                 Hex2Int, Modulus, SigridHash, Cartesian, VocabGen, VocabMap]
