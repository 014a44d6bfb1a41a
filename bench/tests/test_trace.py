"""bench/trace.py: the reduction on hand-made planes, and on a small trace
recorded on a TPU v5e (one chip, ``criteo-vocab.train``)."""

import collections
import gzip
import os

import pytest

from bench import harness, trace, work

Ev = collections.namedtuple("Ev", "name start_ns duration_ns")
Line = collections.namedtuple("Line", "name events")
Plane = collections.namedtuple("Plane", "name lines")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "criteo-vocab-train.xplane.pb.gz")


def _planes():
    host = Plane("/host:CPU", [Line("python3", [
        Ev("bench.traced", 1000, 9000),          # window 1000..10000
        Ev("bench.wait", 1000, 1500),
        Ev("bench.step", 2500, 5500),
        Ev("bench.metrics", 8000, 200),
        Ev("bench.wait", 8700, 1300)])])
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [
            Ev("jit_apply_fn(123)", 500, 2500),   # 1000..3000 inside
            Ev("jit_train_step(9)", 3000, 5000),  # inside
            Ev("jit_apply_fn(123)", 9000, 2000)]),  # 9000..10000 inside
        Line("XLA Ops", [
            Ev("%run.1 = custom-call(...)", 500, 2500),
            Ev("%fusion.4 = fusion(...)", 3000, 1000),
            Ev("%all-reduce.2 = all-reduce(...)", 4500, 500),
            Ev("%fusion.8 = fusion(...)", 5000, 2800),
            Ev("%run.1 = custom-call(...)", 9000, 2000)])])
    other = Plane("/device:CUSTOM:Megascale Trace", [])
    return [host, dev, other]


def test_reduction_by_hand():
    red = trace.reduce_planes(_planes())
    assert red["window_s"] == pytest.approx(9e-6)
    (chip,) = red["chips"]
    # busy: 1000..4000, 4500..7800, 9000..10000
    assert chip["busy_s"] == pytest.approx(7.3e-6)
    assert chip["modules"]["jit_apply_fn"][0] == pytest.approx(3e-6)
    # 2000/2500 of the first execution, 1000/2000 of the last
    assert chip["modules"]["jit_apply_fn"][1] == pytest.approx(1.3)
    assert chip["modules"]["jit_train_step"] == pytest.approx([5e-6, 1.0])
    assert chip["collective_s"] == pytest.approx(5e-7)
    assert chip["ops"]["jit_apply_fn/run.1"] == pytest.approx(3e-6)
    assert chip["ops"]["jit_train_step/all-reduce.2"] == pytest.approx(5e-7)
    # gaps: 4000..4500 in the step; 7800..9000 200 in the step, 200 in
    # metrics, 300 in the next wait, 500 in none
    assert chip["gaps"] == [("bench.wait", pytest.approx(1.2e-6)),
                            ("bench.step", pytest.approx(5e-7))]
    assert red["spans"]["bench.wait"] == [pytest.approx(2.8e-6), 2]
    bd = trace.breakdown(red)
    assert bd["device_ops"][:2] == [
        ["jit_apply_fn/run.1", pytest.approx(3e-6)],
        ["jit_train_step/fusion.8", pytest.approx(2.8e-6)]]
    assert bd["idle_gaps"][0] == ["bench.wait", pytest.approx(1.2e-6)]


def test_readers_on_hand_made_planes():
    red = trace.reduce_planes(_planes())
    run = {"trace": red, "chips": 1,
           "counters": {"traced_steps": 1, "window_steps": 4,
                        "trainer_wait_s": 0.002, "fit_s": 0.8,
                        "fit_batches": 8},
           "peaks": work.peaks("TPU v5 lite"),
           "work": {"rows_per_step": 65536, "train_flops_per_row": 14760192,
                    "etl_bytes_per_batch": 30670848}}
    read = {n: harness.load_reader(n)(run) for n in (
        "etl_device_ms", "step_device_ms", "device_idle_share",
        "trainer_wait_ms", "fit_ms_per_batch", "etl_roofline",
        "train_mfu")}
    assert read["etl_device_ms"] == pytest.approx(1e3 * 3e-6 / 1.3)
    assert read["step_device_ms"] == pytest.approx(5e-3)
    assert read["device_idle_share"] == pytest.approx(100 * 1.7 / 9)
    assert read["trainer_wait_ms"] == pytest.approx(0.5)
    assert read["fit_ms_per_batch"] == pytest.approx(100.0)
    least = 30670848 / 819e9
    assert read["etl_roofline"] == pytest.approx(
        100 * least / (3e-6 / 1.3))
    assert read["train_mfu"] == pytest.approx(
        100 * 14760192 * 65536 / 9e-6 / 197e12)


def test_no_window_span_raises():
    planes = _planes()
    planes[0] = Plane("/host:CPU", [Line("python3", [])])
    with pytest.raises(ValueError, match="bench.traced"):
        trace.reduce_planes(planes)


def test_nothing_to_read_gives_none():
    run = {"trace": None, "counters": {}, "chips": 1}
    for name in ("etl_device_ms", "step_device_ms", "device_idle_share",
                 "trainer_wait_ms", "fit_ms_per_batch", "etl_roofline",
                 "train_mfu"):
        assert harness.load_reader(name)(run) is None


def test_recorded_chip_trace():
    import jax
    with open(DATA, "rb") as fh:
        data = jax.profiler.ProfileData.from_serialized_xspace(
            gzip.decompress(fh.read()))
    red = trace.reduce_planes(data.planes)
    # the recorded window: 3 steps, each the apply program of the next
    # batch and the train step, back to back on the one chip
    assert red["window_s"] == pytest.approx(0.691833107)
    assert red["spans"]["bench.step"] == [pytest.approx(0.678733727), 3]
    (chip,) = red["chips"]
    assert chip["plane"] == "/device:TPU:0"
    assert chip["busy_s"] == pytest.approx(0.687216941)
    assert chip["modules"]["jit_train_step"] == pytest.approx(
        [0.364791254, 3.0])
    assert chip["modules"]["jit_apply_fn"] == pytest.approx(
        [0.322469859, 3.0])
    assert chip["collective_s"] == 0.0
    assert chip["gaps"][0] == ("bench.step", pytest.approx(0.003428398))
    assert trace.breakdown(red)["device_ops"][0] == [
        "jit_apply_fn/run.1", pytest.approx(0.320940858)]
