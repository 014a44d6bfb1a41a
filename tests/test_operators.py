"""Per-operator semantics: numpy oracle == jnp expression, edge cases."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import operators as O

RNG = np.random.default_rng(0)


def check_op(op, x, **kw):
    want = op.numpy(x)
    got = np.asarray(op.jnp_expr(jnp.asarray(x)))
    np.testing.assert_allclose(got, want, rtol=1e-6, **kw)


def test_clamp_basic():
    x = np.array([-5.0, -0.0, 0.5, 99.0], np.float32)
    check_op(O.Clamp(0.0), x)
    assert O.Clamp(0.0).numpy(x).min() == 0.0


def test_clamp_hi():
    x = RNG.normal(size=(100,)).astype(np.float32) * 10
    op = O.Clamp(0.0, 5.0)
    assert op.numpy(x).max() <= 5.0
    check_op(op, x)


def test_logarithm():
    x = np.array([0.0, 999.0, 1e-9], np.float32)
    out = O.Logarithm().numpy(x)
    np.testing.assert_allclose(out[1], np.log(1000.0), rtol=1e-6)
    check_op(O.Logarithm(), x)


def test_log1p_f32_within_one_ulp():
    """log1p_f32 is within 1 ulp of float64 log1p rounded to float32 from
    1e-30 to the float32 maximum and on (-1, 0), and keeps log1p's special
    values."""
    x = np.concatenate([
        np.linspace(0, 10, 50001), np.geomspace(1e-30, 3.4e38, 50001),
        -np.geomspace(1e-9, 0.999, 5001),
        RNG.normal(size=20000) ** 2 * 3]).astype(np.float32)
    want = np.log1p(x.astype(np.float64)).astype(np.float32)
    got = np.asarray(O.log1p_f32(jnp.asarray(x)))
    ulps = np.abs(got.astype(np.float64) - want) / np.spacing(np.abs(want))
    assert ulps.max() <= 1.0, (ulps.max(), x[ulps.argmax()])
    special = np.array([-1.0, -2.0, np.inf, np.nan, 0.0], np.float32)
    np.testing.assert_array_equal(np.asarray(O.log1p_f32(jnp.asarray(special))),
                                  [-np.inf, np.nan, np.inf, np.nan, 0.0])


def test_fill_missing_float():
    x = np.array([3.2, np.nan, -1.0], np.float32)
    out = O.FillMissing(0.0).numpy(x)
    np.testing.assert_allclose(out, np.array([3.2, 0.0, -1.0], np.float32),
                               rtol=1e-6)
    check_op(O.FillMissing(0.0), x)


def test_fill_missing_int():
    x = np.array([7, O.INT_MISSING, -3], np.int32)
    out = O.FillMissing(5).numpy(x)
    np.testing.assert_array_equal(out, [7, 5, -3])
    check_op(O.FillMissing(5), x)


def test_bucketize_paper_example():
    # paper: x=37, bins=[10,20,40] -> bin 3  (wait: 37 >= 10, >= 20, < 40 -> 2)
    op = O.Bucketize([10, 20, 40])
    assert op.numpy(np.array([37.0], np.float32))[0] == 2
    assert op.numpy(np.array([45.0], np.float32))[0] == 3
    assert op.numpy(np.array([5.0], np.float32))[0] == 0
    check_op(op, RNG.normal(size=(64,)).astype(np.float32) * 30)


def test_bucketize_unsorted_raises():
    with pytest.raises(ValueError):
        O.Bucketize([10, 5])


def test_onehot_paper_example():
    # bin=3, K=5 -> [0,0,0,1,0]
    op = O.OneHot(5)
    out = op.numpy(np.array([[3]], np.int64))
    np.testing.assert_array_equal(out[0], [0, 0, 0, 1, 0])
    x = RNG.integers(0, 5, size=(16, 2)).astype(np.int32)
    check_op(op, x)


def test_onehot_out_of_range_all_zero():
    out = O.OneHot(4).numpy(np.array([[7]], np.int64))
    assert out.sum() == 0


def test_hex2int_paper_example():
    # "0x1a3f" -> 6719 (without the 0x prefix, width 4)
    op = O.Hex2Int(4)
    x = np.frombuffer(b"1a3f", np.uint8).reshape(1, 1, 4)
    assert op.numpy(x)[0, 0] == 0x1A3F == 6719
    got = np.asarray(op.jnp_expr(jnp.asarray(x)))
    assert got[0, 0] == 6719


def test_hex2int_case_and_overflow():
    op = O.Hex2Int(8)
    for s, want in [(b"ffffffff", -1), (b"FFFFFFFF", -1),
                    (b"80000000", -(2 ** 31)), (b"7fffffff", 2 ** 31 - 1)]:
        x = np.frombuffer(s, np.uint8).reshape(1, 1, 8)
        assert op.numpy(x)[0, 0] == want, s
        assert np.asarray(op.jnp_expr(jnp.asarray(x)))[0, 0] == want, s


def test_hex2int_missing_sentinel():
    x = np.zeros((1, 1, 8), np.uint8)  # all-zero string = missing
    assert O.Hex2Int(8).numpy(x)[0, 0] == O.INT_MISSING


def test_modulus_paper_example():
    op = O.Modulus(5)
    assert op.numpy(np.array([-7], np.int32))[0] == 3
    x = RNG.integers(-(2 ** 31), 2 ** 31 - 1, size=(1000,)).astype(np.int32)
    out = op.numpy(x)
    assert out.min() >= 0 and out.max() < 5
    check_op(op, x)


def test_sigrid_hash_range_and_determinism():
    op = O.SigridHash(1000)
    x = RNG.integers(-(2 ** 31), 2 ** 31 - 1, size=(5000,)).astype(np.int32)
    out = op.numpy(x)
    assert out.min() >= 0 and out.max() < 1000
    np.testing.assert_array_equal(out, op.numpy(x))  # deterministic
    check_op(op, x)
    # distribution sanity: all buckets of a small mod get hit
    small = O.SigridHash(8).numpy(x)
    assert len(np.unique(small)) == 8


def test_cartesian_binary():
    op = O.Cartesian(m=997)
    a = RNG.integers(0, 1000, size=(500,)).astype(np.int32)
    b = RNG.integers(0, 1000, size=(500,)).astype(np.int32)
    out = op.numpy2(a, b)
    assert out.min() >= 0 and out.max() < 997
    got = np.asarray(op.jnp_expr2(jnp.asarray(a), jnp.asarray(b)))
    np.testing.assert_array_equal(got, out)
    # asymmetric: cross(a,b) != cross(b,a) in general
    assert not np.array_equal(out, op.numpy2(b, a))


def test_vocab_gen_first_appearance_order():
    vg = O.VocabGen(capacity=16)
    st = vg.init_state()
    st = vg.update(st, np.array([5, 3, 5, 7, 3, 0], np.int32), 0)
    table = vg.finalize(st)
    # 5 seen first -> 0; 3 -> 1; 7 -> 2; 0 -> 3
    assert table[5] == 0 and table[3] == 1 and table[7] == 2 and table[0] == 3
    assert O.VocabGen.n_unique(table) == 4
    assert (table == -1).sum() == 12


def test_vocab_gen_rejects_out_of_range():
    vg = O.VocabGen(capacity=4)
    with pytest.raises(ValueError):
        vg.update(vg.init_state(), np.array([9], np.int32), 0)


def test_vocab_map_oov():
    vg = O.VocabGen(capacity=8)
    st = vg.update(vg.init_state(), np.array([1, 2], np.int32), 0)
    table = vg.finalize(st)
    vm = O.VocabMap(8)
    out = vm.numpy_apply(np.array([[1, 2, 5]], np.int32), table)
    np.testing.assert_array_equal(out, [[0, 1, 2]])  # 5 unseen -> OOV == 2


def test_vocab_gen_frequency_filter():
    """min_count drops rare values (paper §3.2.2 frequency-based filtering):
    they vanish from the table and map to OOV at apply time."""
    vg = O.VocabGen(capacity=16, min_count=2)
    st = vg.init_state()
    st = vg.update(st, np.array([5, 3, 5, 7, 3, 5], np.int32), 0)
    table = vg.finalize(st)
    # 5 (x3) and 3 (x2) survive in first-appearance order; 7 (x1) filtered
    assert table[5] == 0 and table[3] == 1 and table[7] == -1
    assert O.VocabGen.n_unique(table) == 2
    out = O.VocabMap(16).numpy_apply(np.array([[5, 3, 7]], np.int32), table)
    np.testing.assert_array_equal(out, [[0, 1, 2]])  # 7 -> OOV (== n_unique)


def test_vocab_gen_min_count_one_keeps_all():
    vg1 = O.VocabGen(capacity=8, min_count=1)
    vg0 = O.VocabGen(capacity=8)
    x = np.array([1, 2, 2, 4], np.int32)
    t1 = vg1.finalize(vg1.update(vg1.init_state(), x, 0))
    t0 = vg0.finalize(vg0.update(vg0.init_state(), x, 0))
    np.testing.assert_array_equal(t1, t0)
