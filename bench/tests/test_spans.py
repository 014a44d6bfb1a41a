"""bench/spans.py and the readers of the program's spans: a trace recorded
on the CPU around a running executor, hand-made planes, the names the
trace readers rely on, and a short trace recorded on a TPU v5e (one chip,
``criteo-vocab.train``) with the program's spans."""

import collections
import glob
import gzip
import os
import re

import numpy as np
import pytest

from bench import harness, spans, trace

Ev = collections.namedtuple("Ev", "name start_ns duration_ns stats",
                            defaults=((),))
Line = collections.namedtuple("Line", "name events")
Plane = collections.namedtuple("Plane", "name lines")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "criteo-vocab-train-spans.xplane.pb.gz")
READERS = ("read_ms_per_batch", "transform_host_ms", "h2d_ms_per_batch")
STAGES = ("etl.read", "etl.transform", "etl.place", "etl.deliver")


def _record(tmp_path, body):
    """Planes of a profile recorded on the CPU around ``body()``."""
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    return list(jax.profiler.ProfileData.from_file(path).planes)


def _by_batch(events, name):
    return {ev["args"]["batch"]: ev for ev in events
            if ev["name"] == name and "batch" in ev["args"]}


def test_executor_spans_join_on_batch(tmp_path):
    import jax
    import jax.numpy as jnp

    from repro.etl_runtime.runtime import StreamingExecutor
    from repro.training.train_loop import LoopConfig, train_loop

    rng = np.random.default_rng(0)
    batches = [{"dense": rng.normal(size=(64, 13)).astype(np.float32),
                "ids": rng.integers(0, 99, size=(64, 26), dtype=np.int32),
                "label": rng.integers(0, 2, size=64).astype(np.float32)}
               for _ in range(4)]
    apply = jax.jit(lambda cols: {k: v * 2 for k, v in cols.items()})
    State = collections.namedtuple("State", "step")

    def step_fn(state, batch):
        return State(state.step + 1), {"loss": jnp.sum(batch["dense"])}

    ex = StreamingExecutor(apply, iter(batches), credits=2)
    planes = _record(tmp_path, lambda: train_loop(
        State(jnp.int32(0)), step_fn, ex, LoopConfig(total_steps=99),
        async_ckpt=False))
    events = spans.program_events(planes)
    nbytes = [sum(v.nbytes for v in b.values()) for b in batches]
    joined = {name: _by_batch(events, name) for name in STAGES}
    for name in STAGES:
        assert sorted(joined[name]) == [0, 1, 2, 3], name
    # the read and the consumer's wait that met the stream's end carry no
    # batch; nothing else does
    ended = [ev["name"] for ev in events
             if ev["name"].startswith("etl.") and "batch" not in ev["args"]]
    assert sorted(ended) == ["etl.deliver", "etl.read"]
    for b in range(4):
        rd, tf, pl, dl = (joined[name][b] for name in STAGES)
        assert rd["end_ns"] <= tf["start_ns"] <= tf["end_ns"] <= pl["start_ns"]
        assert pl["end_ns"] <= dl["end_ns"]
        assert tf["args"]["bytes"] == nbytes[b]
        assert rd["line"] != tf["line"] != pl["line"]
    assert ex.stats.stages["transform"].bytes == sum(nbytes)
    assert ex.stats.stages["transform"].as_dict()["bytes"] == sum(nbytes)
    steps = sorted(ev["args"]["step_num"] for ev in events
                   if ev["name"] == "train.step")
    assert steps == [0, 1, 2, 3]
    red = spans.program_spans(planes)
    assert {n: red[n]["count"] for n in STAGES} == {
        "etl.read": 5, "etl.transform": 4, "etl.place": 4, "etl.deliver": 5}
    assert red["train.step"]["count"] == 4
    assert red["etl.transform"]["total_s"] == pytest.approx(
        sum(ev["end_ns"] - ev["start_ns"]
            for ev in joined["etl.transform"].values()) / 1e9)


def test_stage_bytes_exported():
    from repro.etl_runtime.metrics import stats_to_prometheus
    from repro.etl_runtime.runtime import RuntimeStats, StageStats
    stats = RuntimeStats()
    stats.stages["transform"] = StageStats("transform", bytes=17301504)
    text = stats_to_prometheus(stats)
    assert "# TYPE repro_etl_stage_bytes_total counter" in text
    assert re.search(r'repro_etl_stage_bytes_total\{[^}]*stage="transform"'
                     r'[^}]*\} 17301504\b', text)


def _planes():
    py = Line("python3", [
        Ev("bench.traced", 1000, 9000),                 # window 1000..10000
        Ev("etl.deliver", 1100, 400, (("depth", 1), ("batch", 0))),
        Ev("train.step", 1600, 2400, (("step_num", 7),)),
        Ev("etl.deliver", 8000, 1500, (("depth", 0), ("batch", 1)))])
    rd = Line("python3", [
        Ev("etl.read", 500, 1000, (("batch", 1),)),     # starts before
        Ev("etl.read", 2000, 1000, (("batch", 2),)),
        Ev("etl.read", 9500, 1000, (("batch", 3),))])   # ends after
    tf = Line("python3", [
        Ev("etl.transform", 3000, 2000, (("batch", 1), ("bytes", 64))),
        Ev("etl.transform", 6000, 1000, (("batch", 2), ("bytes", 64)))])
    # transfers on two runtime threads: 3500..4500 and 4000..5500 overlap
    # (union 3500..5500, 1500 inside the first transform span), 6800..7500
    # half inside the second, 9000..9200 in no transform span
    t1 = Line("pjrt-tpu-tasks/1", [
        Ev("XlaLinearize", 3500, 1000),
        Ev("tpu::System::TransferToDevice", 6800, 700),
        Ev("Transpose", 3600, 100)])
    t2 = Line("pjrt-tpu-tasks/2", [
        Ev("tpu::System::TransferToDevice", 4000, 1500),
        Ev("H2D Dispatch", 4000, 5000),
        Ev("XlaLinearize", 9000, 200)])
    host = Plane("/host:CPU", [py, rd, tf, t1, t2])
    dev = Plane("/device:TPU:0", [Line("XLA Ops", [
        Ev("%etl_apply_group.1 = custom-call(...)", 1000, 4000),
        Ev("%fusion.4 = fusion(...)", 5500, 2000)])])
    return [host, dev]


def test_program_spans_by_hand():
    red = spans.program_spans(_planes())
    assert set(red) == {"etl.deliver", "train.step", "etl.read",
                        "etl.transform"}
    assert red["etl.read"] == {"total_s": pytest.approx(1e-6), "count": 1}
    assert red["etl.deliver"] == {"total_s": pytest.approx(1.9e-6),
                                  "count": 2}
    tf = red["etl.transform"]
    assert tf["count"] == 2 and tf["total_s"] == pytest.approx(3e-6)
    # inside 3000..5000: 3500..5000 (1500); inside 6000..7000: 6800..7000
    assert tf["h2d_s"] == pytest.approx(1.7e-6)
    # the union over the window: 3500..5500, 6800..7500, 9000..9200
    assert tf["h2d_window_s"] == pytest.approx(2.9e-6)


def test_idle_gap_spans_by_hand():
    planes = _planes()
    gaps = spans.idle_gap_spans(planes)
    # device busy 1000..5000 and 5500..7500: gaps 7500..10000 (2500) and
    # 5000..5500 (500), listed as bench/trace.py lists them
    red = trace.reduce_planes(planes)
    assert [g[:2] for g in gaps] == [list(g) for g in
                                     trace.breakdown(red)["idle_gaps"]]
    (label, d, held), (_, _, held2) = gaps
    assert d == pytest.approx(2.5e-6)
    # etl.deliver of batch 1 covers 8000..9500 of it, more than half
    assert held == [["etl.deliver", "python3#0", 1]]
    # 5000..5500 lies in no program span
    assert held2 == []


def _readers(run):
    return {n: harness.load_reader(n)(run) for n in READERS}


def test_readers_on_hand_made_planes():
    run = {"trace": {"window_s": 9e-6,
                     "program_spans": spans.program_spans(_planes())}}
    read = _readers(run)
    assert read["read_ms_per_batch"] == pytest.approx(1e-3)
    assert read["transform_host_ms"] == pytest.approx(1.5e-3)
    assert read["h2d_ms_per_batch"] == pytest.approx(0.85e-3)


def test_readers_give_none_without_spans(tmp_path, monkeypatch):
    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))
    assert _readers({"trace": None}) == dict.fromkeys(READERS)
    # a program without spans: the parent of the spans, or bench spans only
    bare = [Plane("/host:CPU", [Line("python3", [
        Ev("bench.traced", 0, 100), Ev("bench.step", 10, 50)])])]
    run = {"trace": {"window_s": 1e-7,
                     "program_spans": spans.program_spans(bare)}}
    assert _readers(run) == dict.fromkeys(READERS)
    # spans, but no runtime transfer events (a trace of the CPU)
    planes = _planes()
    planes[0] = Plane("/host:CPU", planes[0].lines[:3])
    run = {"trace": {"window_s": 9e-6,
                     "program_spans": spans.program_spans(planes)}}
    read = _readers(run)
    assert read["h2d_ms_per_batch"] is None
    assert read["transform_host_ms"] == pytest.approx(1.5e-3)
    # no profile of the run on disk
    assert _readers({"trace": {"window_s": 9e-6}}) == dict.fromkeys(READERS)


def test_for_run_finds_the_runs_profile(tmp_path, monkeypatch):
    import jax
    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))
    run_dir = tmp_path / "bench-x" / "trace"

    def body():
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            for i in range(3):
                with jax.profiler.TraceAnnotation("etl.read", batch=i):
                    np.ones(1000).sum()

    planes = _record(run_dir, body)
    # a stale file beside it that is no profile
    (tmp_path / "bench-y" / "trace").mkdir(parents=True)
    (tmp_path / "bench-y" / "trace" / "h.xplane.pb").write_bytes(b"junk")
    w0, w1 = spans.window(planes)
    found = spans.for_run({"trace": {"window_s": (w1 - w0) / 1e9}})
    assert found["etl.read"]["count"] == 3
    assert spans.for_run({"trace": {"window_s": 12.5}}) == {}
    read = _readers({"trace": {"window_s": (w1 - w0) / 1e9}})
    assert read["read_ms_per_batch"] == pytest.approx(
        1e3 * found["etl.read"]["total_s"] / 3)
    assert read["h2d_ms_per_batch"] is None


def test_module_names_the_readers_use():
    """The XLA modules the device-trace readers look up are the names the
    program's jitted apply and train step lower to."""
    import jax
    import jax.numpy as jnp

    from bench.metrics import etl_device_ms, step_device_ms
    from repro.configs.base import TrainConfig
    from repro.core.pipeline import paper_pipeline
    from repro.data import synth
    from repro.training import train_loop as tl

    cp = paper_pipeline("II", batch_size=64, small_vocab=512).compile(
        backend="pallas")
    raw = next(synth.dataset_batches("I", rows=64, batch_size=64, seed=0))
    tables, n_uniq = cp._staged_table_args()
    cols = {k: jnp.asarray(v) for k, v in cp._raw_columns(raw).items()}
    apply_text = cp._apply_jit.lower(tables, n_uniq, cp._resolved_tables(),
                                     cols).as_text()
    step = tl.make_train_step(lambda p, b: jnp.sum(p["w"] * b["x"]),
                              TrainConfig())
    state = tl.TrainState.create({"w": jnp.ones(4)}, TrainConfig())
    step_text = jax.jit(step).lower(state, {"x": jnp.ones(4)}).as_text()
    module = re.compile(r"^module @(\S+)", re.M)
    assert module.search(apply_text).group(1) == etl_device_ms.MODULE
    assert module.search(step_text).group(1) == step_device_ms.MODULE
    # the named scopes sit below the module names
    assert "etl_apply/" in cp._apply_jit.lower(
        tables, n_uniq, cp._resolved_tables(), cols).as_text(debug_info=True)
    assert "train_step/" in jax.jit(step).lower(
        state, {"x": jnp.ones(4)}).as_text(debug_info=True)


def test_recorded_chip_trace_with_program_spans():
    """A 3 s window of ``criteo-vocab.train`` on one v5e (seed
    3,600,000,021), recorded with the program's spans."""
    import jax
    with open(DATA, "rb") as fh:
        planes = list(jax.profiler.ProfileData.from_serialized_xspace(
            gzip.decompress(fh.read())).planes)
    red = spans.program_spans(planes)
    assert {n: s["count"] for n, s in red.items()} == dict.fromkeys(
        STAGES + ("train.step",), 12)
    tf = red["etl.transform"]
    assert tf["total_s"] == pytest.approx(0.301548048)
    assert tf["h2d_s"] == pytest.approx(0.228114927)
    # every host-to-device transfer of the window lies in a transform span
    assert tf["h2d_window_s"] == pytest.approx(tf["h2d_s"])
    events = spans.program_events(planes)
    joined = {name: _by_batch(events, name) for name in STAGES}
    assert sorted(set.intersection(*(set(j) for j in joined.values()))) == [
        14, 15, 16]
    assert {ev["args"]["bytes"] for ev in joined["etl.transform"].values()} \
        == {17301504}
    # read, transform and place each on a thread of their own, the wait
    # for a batch on the trainer's
    lines = {name: {ev["line"] for ev in events if ev["name"] == name}
             for name in STAGES + ("train.step",)}
    assert all(len(v) == 1 for v in lines.values())
    assert lines["etl.deliver"] == lines["train.step"]
    assert len(set.union(*lines.values())) == 4
    read = _readers({"trace": {"window_s": 3.235611431,
                               "program_spans": red}})
    assert read == {"read_ms_per_batch": pytest.approx(575.384866 / 12),
                    "transform_host_ms": pytest.approx(301.548048 / 12),
                    "h2d_ms_per_batch": pytest.approx(228.114927 / 12)}
    # the device names the apply kernel as the program does
    bd = trace.breakdown(trace.reduce_planes(planes))
    assert bd["device_ops"][0] == ["jit_apply_fn/etl_apply_group.1",
                                   pytest.approx(1.768116497)]
    # the window's one longer gap: the trainer waited while the transform
    # thread handed batch 11 to the device
    gaps = spans.idle_gap_spans(planes)
    assert [g[:2] for g in gaps] == [list(g) for g in bd["idle_gaps"]]
    assert gaps[0][2] == [["etl.transform", "python3#17", 11]]
