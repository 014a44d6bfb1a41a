"""One run of one cell: set-up, measured window, trace, output check.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration in ``bench/configs/``, its traffic mix in
``bench/traffic/`` and each per-layer metric's reader in
``bench/metrics/<metric>.py``.  The configuration names the rest: the
program's schema (a ``Schema`` constructor), the ``paper_pipeline``
arguments, the kernel paths the pipeline has to lower to, its plain ETL
reference ``bench/reference/<etl_reference>.py`` and its model kind, whose
reference is ``bench/reference/<model>.py`` and whose program is built by
``bench/models/<model>.py``.  ``bench/run.py`` is the command line.

A run, for a training cell:

1. Set-up (``setup_s``, from process start to window open): the traffic
   pool drawn from the seed and written as columnar shards; the
   ``EtlJob`` over a source that loops over them; the fit program warmed
   on one batch, then ``job.fit()`` timed over the pool; the apply program
   warmed; the weights drawn from the seed on the device; the executor
   started, the train step compiled for the delivered batch's shapes;
   three checked steps and two warm steps through the window's own
   ``train_loop`` and step.
2. The window: ``job.batches()`` into ``train_loop`` with the compiled
   step until ``--seconds`` have passed; each step's completion time is
   taken when its loss is ready.
3. With ``--trace 1`` the whole window is profiled; spans
   ``bench.wait`` (next batch), ``bench.step`` (the step call, to its
   loss) and ``bench.metrics`` label what the host was doing.
4. After the window: the peak device memory, then the program's state
   freed and the output check (``bench/check.py``) against the plain
   references under ``bench/reference/``.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import resource
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import check, gen, work
from bench import trace as trace_lib

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
CHECKED_STEPS = 3
WARM_STEPS = 2
WINDOW_SAMPLES = 2
DRAIN_SECONDS = 1.0


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------

def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


def load_cell(bench: dict, workload: str, root: str = ROOT) -> tuple:
    """(cell, config, mix) of the cell named ``workload``."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, cfg_entry["file"])) as fh:
        config = json.load(fh)
    mix = gen.load_mix(cell["traffic"], os.path.join(root, "bench", "traffic"))
    return cell, config, mix


def cell_metrics(bench: dict, workload: str) -> tuple:
    """(end-to-end, per-layer) metric entries that ``workload`` reports."""
    def applies(m):
        return "workloads" not in m or workload in m["workloads"]
    e2e = [m for m in bench["end_to_end"] if applies(m)]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if applies(m) and m["moves"] in names]
    return e2e, layer


_MODULES: dict = {}


def load_module(kind: str, name: str, root: str = ROOT):
    """The module ``bench/<kind>/<name>.py`` under ``root``, loaded once."""
    path = os.path.join(root, "bench", kind, f"{name}.py")
    if path not in _MODULES:
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{len(_MODULES)}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def load_reader(name: str, root: str = ROOT):
    return load_module("metrics", name, root).read


class Parts:
    """What a configuration names, resolved: the ETL reference, the model's
    reference and program, and the widths the reference reads."""

    def __init__(self, config: dict, root: str = ROOT):
        self.etl = load_module("reference", config["etl_reference"], root)
        self.model_ref = load_module("reference", config["model"], root)
        self.model = load_module("models", config["model"], root)
        self.widths = self.model_ref.widths(config, self.etl.id_rows(config))


# ---------------------------------------------------------------------------
# set-up pieces
# ---------------------------------------------------------------------------

class CompileCounter:
    """Counts backend compiles and persistent-cache loads as JAX reports
    them, so that the window can show it compiled nothing."""

    def __init__(self):
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def total(self) -> int:
        return self.compiles + self.cache_hits


def looping_source(pool_dir: str):
    """The pool's shards, read from disk in a loop for as long as the
    executor pulls."""
    from repro.data import columnar
    from repro.data.source import Source

    def reader(spec):
        cols = list(spec.columns) if spec.columns is not None else None
        while True:
            yield from columnar.iter_shards(pool_dir, cols)

    return Source(reader, name=f"loop:{pool_dir}",
                  native=frozenset({"columns"}),
                  schema=columnar.load_schema(pool_dir))


def train_config(config: dict):
    """The program's ``TrainConfig`` for the configuration's optimizer."""
    from repro.configs.base import TrainConfig
    o = config["optimizer"]
    return TrainConfig(optimizer=o["name"], lr=o["lr"], beta1=o["beta1"],
                       beta2=o["beta2"], eps=o["eps"],
                       weight_decay=o["weight_decay"],
                       max_grad_norm=o["max_grad_norm"])


def require_lowering(cp, expect: dict, platform: str) -> None:
    """Refuse a run whose pipeline does not lower as the configuration
    states: Pallas in interpret mode on a TPU, or an apply output or a
    vocabulary fit on another path than ``expect["apply"]`` /
    ``expect["fit"]``."""
    if platform == "tpu" and cp.interpret:
        raise RuntimeError("Pallas kernels in interpret mode on a TPU")
    for what, report in (("apply", cp.lowering_report()),
                         ("fit", cp.fit_lowering_report())):
        for name, rep in report.items():
            log(f"{what} {name}: path={rep['path']} "
                f"reason_kind={rep['reason_kind']!r}")
            if rep["path"] != expect[what]:
                raise RuntimeError(
                    f"{what} {name} lowered to {rep['path']!r}, the "
                    f"configuration states {expect[what]!r}: "
                    f"{rep['reason']}")


@jax.jit
def _norms(tree, minus=None):
    if minus is not None:
        tree = jax.tree_util.tree_map(lambda a, b: a - b, tree, minus)
    return jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


def _leaf_norms(tree, minus=None) -> dict:
    """Every leaf's norm (of ``tree - minus`` when given), by leaf path."""
    return {jax.tree_util.keystr(k): float(v) for k, v in
            jax.tree_util.tree_flatten_with_path(_norms(tree, minus))[0]}


def _host(batch: dict) -> dict:
    return {k: np.asarray(v) for k, v in batch.items()}


def _peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def run_cell(bench: dict, workload: str, seed: int, seconds: float,
             trace: bool, *, t_process: float, cell=None, config=None,
             mix=None, trace_dir: str | None = None) -> dict:
    """Run one cell once and return its result line (a dict).

    ``cell``, ``config`` and ``mix`` default to the files named in
    ``bench``; a test hands smaller ones in.  ``trace_dir`` keeps the
    profile there instead of a temporary directory.
    """
    from repro.core.pipeline import paper_pipeline
    from repro.core.schema import Schema
    from repro.data import columnar
    from repro.data.source import Source
    from repro.session import EtlJob
    from repro.training import train_loop as tl

    if cell is None:
        cell, config, mix = load_cell(bench, workload)
    e2e_metrics, layer_metrics = cell_metrics(bench, workload)
    chips = cell["chips"]
    if chips != 1:
        raise ValueError(f"cell {workload} asks for {chips} chips; the "
                         f"harness runs one-chip cells only")
    devices = jax.devices()[:chips]
    platform = devices[0].platform
    log(f"cell {workload}: config {config['name']}, traffic "
        f"{cell['traffic']}, chips {chips}, seed {seed}, "
        f"platform {platform}, device_kind "
        f"{devices[0].device_kind}, devices {len(jax.devices())}")
    # the checkout's own cache, whatever the environment names: the path is
    # part of the cache key, and two checkouts must share nothing
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # no eviction: an entry written without eviction's access-time file
    # (any run with the size limit unset) makes every later write fail
    jax.config.update("jax_compilation_cache_max_size", -1)
    log(f"compile cache: {CACHE_DIR}")
    counter = CompileCounter()
    phases = {}
    mark = [time.perf_counter()]

    def phase(name):
        now = time.perf_counter()
        phases[name] = now - mark[0]
        mark[0] = now
        log(f"set-up {name}: {phases[name]:.3f}s")

    phases["process_start"] = mark[0] - t_process
    parts = Parts(config)
    pipe_cfg, widths = config["pipeline"], parts.widths
    pipe_kw = dict(pipe_cfg["paper_pipeline"])
    which = pipe_kw.pop("which")
    rows = pipe_kw["batch_size"]
    if mix["batch_rows"] != rows:
        raise ValueError(f"traffic batch_rows {mix['batch_rows']} != "
                         f"pipeline batch_size {rows}")
    schema = getattr(Schema, pipe_cfg["schema"])()
    if [f.name for f in schema] != gen.column_names(mix):
        raise ValueError(f"traffic {cell['traffic']} does not draw the "
                         f"columns of schema {pipe_cfg['schema']}")
    tmp_root = tempfile.mkdtemp(prefix="bench-")
    try:
        # ---- data pool ---------------------------------------------------
        pool = gen.gen_pool(mix, seed)
        pool_dir = os.path.join(tmp_root, "pool")
        columnar.write_dataset(pool_dir, schema, iter(pool))
        phase("data_pool")

        # ---- ETL job, fit, apply warm-up ----------------------------------
        job = EtlJob(paper_pipeline(which, schema, **pipe_kw),
                     looping_source(pool_dir), backend=pipe_cfg["backend"],
                     fit_source=Source.columnar(pool_dir))
        cp = job.compiled
        log(f"pallas interpret={cp.interpret}")
        require_lowering(cp, pipe_cfg["lowering"], platform)
        fits = bool(cp.fit_lowering_report())
        if fits:
            job.fit(source=Source.stream([pool[0]]))
            phase("fit_warm")
        t_fit = time.perf_counter()
        job.fit()
        tables = [np.asarray(v) for v in job.state.tables.values()]
        fit_s = time.perf_counter() - t_fit
        phase("fit")
        jax.block_until_ready(job.apply(pool[0]))
        phase("apply_warm")
        log(f"traced_pallas_call_count "
            f"apply={cp.traced_pallas_call_count(pool[0])} "
            f"fit={cp.traced_pallas_call_count(pool[0], phase='fit')}")

        # ---- weights and train state ---------------------------------------
        _, loss = parts.model.build(widths)
        tcfg = train_config(config)
        step = tl.make_train_step(loss, tcfg)
        key = parts.model_ref.weight_key(seed)

        def init_params(k):
            return parts.model_ref.init(k, widths)

        def make_state(k):
            return tl.TrainState.create(init_params(k), tcfg)

        counters = {"fit_s": fit_s, "fit_batches": len(pool) if fits else 0}
        prog = {"losses": []}
        kept = {}
        done_t, wait_s, step_s = [], [], []
        trace_info = {}
        loop_cfg = tl.LoopConfig(total_steps=2 ** 62, log_every=1)

        def record(m):
            with jax.profiler.TraceAnnotation("bench.metrics"):
                done_t.append(time.perf_counter())
                prog["losses"].append(m["loss"])

        with job.batches() as ex:
            it = iter(ex)

            def next_batch():
                t = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.wait"):
                    b = next(it)
                wait_s.append(time.perf_counter() - t)
                return b

            first = next_batch()
            jitted = jax.jit(step, donate_argnums=(0, 1))
            state = jax.jit(make_state)(key)
            jax.block_until_ready(state)
            phase("state_init")
            compiled = jitted.lower(state, first).compile()
            phase("step_compile")

            def step_fn(s, b):
                t = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.step"):
                    s, metrics = compiled(s, b)
                    jax.block_until_ready(metrics["loss"])
                step_s.append(time.perf_counter() - t)
                return s, metrics

            def run(state, batches):
                return tl.train_loop(state, step_fn, batches, loop_cfg,
                                     async_ckpt=False, on_metrics=record)

            # checked steps: the window's own call and feed, rows all new
            batch = first
            for i in range(CHECKED_STEPS):
                if i:
                    batch = next_batch()
                kept[("checked", i)] = _host(batch)
                state = run(state, [batch])
                if i == 0:
                    m1 = _leaf_norms(state.opt["m"])
                    prog["grad_norms"] = {k: v / (1 - tcfg.beta1)
                                          for k, v in m1.items()}
            p0 = jax.jit(init_params)(key)
            prog["change_norms"] = _leaf_norms(state.params, p0)
            del p0
            state = run(state, [next_batch() for _ in range(WARM_STEPS)])
            phase("checked_and_warm_steps")
            n_before = CHECKED_STEPS + WARM_STEPS

            # ---- the window ------------------------------------------------
            rng = np.random.default_rng([seed % 2 ** 63, 1])
            span = max(WINDOW_SAMPLES, int(seconds * 2))
            sample_at = set(rng.choice(span, WINDOW_SAMPLES,
                                       replace=False).tolist())
            if trace:
                jax.profiler.start_trace(
                    trace_dir or os.path.join(tmp_root, "trace"),
                    profiler_options=_profile_options())
            wait0 = ex.stats.consumer_wait_s
            compiles0 = counter.total()
            n_wait0, n_step0 = len(wait_s), len(step_s)
            setup_s = time.perf_counter() - t_process
            log(f"set-up total: {setup_s:.3f}s")

            def window():
                t_open = time.perf_counter()
                trace_info["t_open"] = t_open
                deadline = t_open + seconds
                span = jax.profiler.TraceAnnotation(trace_lib.WINDOW_SPAN)
                if trace:
                    span.__enter__()
                i = 0
                while time.perf_counter() < deadline:
                    b = next_batch()
                    if i in sample_at:
                        kept[("window", i)] = _host(b)
                    i += 1
                    yield b
                if trace:
                    span.__exit__(None, None, None)
                    trace_info["t_traced_close"] = time.perf_counter()
                    # let the program running at the close end before the
                    # profiler stops: an execution the stop cuts is recorded
                    # with a cut duration, and would count as a whole one
                    time.sleep(DRAIN_SECONDS)
                    jax.profiler.stop_trace()

            n_done0 = len(done_t)
            host0 = HostReadings()
            state = run(state, window())
            host = host0.since()
            t_close = done_t[-1] if len(done_t) > n_done0 else time.perf_counter()
            window_done = done_t[n_done0:]
            counters["trainer_wait_s"] = ex.stats.consumer_wait_s - wait0
            counters["window_steps"] = len(window_done)
            window_compiles = counter.total() - compiles0
        window_losses = prog["losses"][n_done0:]
        prog["losses"] = prog["losses"][:CHECKED_STEPS]
        log(f"window: {len(window_done)} steps in "
            f"{t_close - trace_info['t_open']:.3f}s, compiles or cache "
            f"loads inside it: {window_compiles}")
        intervals = np.diff([trace_info["t_open"]] + window_done)
        log_stalls(intervals, wait_s[n_wait0:], step_s[n_step0:], host)
        memory_peak = _peak_bytes(devices)
        log(f"peak_bytes_in_use={memory_peak} "
            f"({memory_peak / 2 ** 30:.2f} GiB)")
        del state, compiled, jitted, first, batch
        gc.collect()

        # ---- end-to-end metrics ---------------------------------------------
        window_s = t_close - trace_info["t_open"]
        values = {
            "train_rows_per_s": len(window_done) * rows / window_s,
            "step_p90_ms": 1e3 * float(np.percentile(intervals, 90)),
            "setup_s": setup_s,
        }

        # ---- output check ----------------------------------------------------
        t_check = time.perf_counter()
        numbers = _check(config, parts, seed, pool, tables, kept, n_before,
                         prog)
        correct, checks = check.judge(numbers,
                                      config.get("correct_limits", {}))
        log(f"output check took {time.perf_counter() - t_check:.3f}s; "
            f"dead leaves left out: {numbers.get('dead_leaves')}")

        # ---- per-layer metrics ------------------------------------------------
        device = {"platform": platform,
                  "kind": devices[0].device_kind,
                  "count": len(jax.devices()),
                  "memory_peak_bytes": memory_peak}
        result = {"correct": bool(correct),
                  "attempted": len(window_done),
                  "failed": int(sum(1 for x in window_losses
                                    if not math.isfinite(x)))}
        if trace:
            red = trace_lib.reduce_file(trace_lib.find_xplane(
                trace_dir or os.path.join(tmp_root, "trace")))
            t0, t1 = trace_info["t_open"], trace_info["t_traced_close"]
            counters["traced_steps"] = sum(1 for t in window_done
                                           if t0 <= t <= t1)
            ctx = {"trace": red, "counters": counters, "config": config,
                   "mix": mix, "chips": chips,
                   "peaks": work.peaks(devices[0].device_kind),
                   "work": {"rows_per_step": rows,
                            "train_flops_per_row":
                                parts.model.train_flops_per_row(widths),
                            "etl_bytes_per_batch": work.etl_bytes_per_batch(
                                config, mix,
                                parts.etl.table_capacities(config))["total"]}}
            metrics = {}
            for m in layer_metrics:
                v = load_reader(m["name"])(ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            device["busy_s"] = (sum(c["busy_s"] for c in red["chips"])
                                / len(red["chips"]))
            device["window_s"] = red["window_s"]
            result["metrics"] = metrics
            result["device"] = device
            result["breakdown"] = trace_lib.breakdown(red)
            log(f"traced window {red['window_s']:.3f}s, "
                f"{counters['traced_steps']} steps, host spans "
                f"{ {k: [round(v[0], 4), v[1]] for k, v in red['spans'].items()} }")
            for chip in red["chips"]:
                log(f"{chip['plane']}: busy {chip['busy_s']:.4f}s, modules "
                    f"{ {k: [round(v[0], 4), v[1]] for k, v in chip['modules'].items()} }")
        else:
            result["metrics"] = {
                m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                for m in e2e_metrics}
            result["device"] = device
        result["setup_phases_s"] = phases
        result["checks"] = checks
        return result
    finally:
        shutil.rmtree(tmp_root, ignore_errors=True)


class HostReadings:
    """What the host did over the window, for finding stalls: garbage
    collections and their pauses, this process's involuntary context
    switches and CPU time, and the machine's stolen CPU time."""

    def __init__(self):
        self.gc = []
        self._gc_t = None
        gc.callbacks.append(self._on_gc)
        self.t0 = time.perf_counter()
        self.cpu0 = time.process_time()
        self.ru0 = resource.getrusage(resource.RUSAGE_SELF)
        self.steal0 = _steal_ticks()

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t = time.perf_counter()
        elif self._gc_t is not None:
            self.gc.append((info["generation"],
                            time.perf_counter() - self._gc_t))

    def since(self) -> dict:
        gc.callbacks.remove(self._on_gc)
        ru = resource.getrusage(resource.RUSAGE_SELF)
        steal = _steal_ticks()
        return {"wall_s": time.perf_counter() - self.t0,
                "cpu_s": time.process_time() - self.cpu0,
                "nivcsw": ru.ru_nivcsw - self.ru0.ru_nivcsw,
                "steal_s": (None if steal is None or self.steal0 is None
                            else (steal - self.steal0)
                            / os.sysconf("SC_CLK_TCK")),
                "gc_runs": len(self.gc),
                "gc_max_ms": 1e3 * max((p for _, p in self.gc), default=0.0),
                "gc_gen2": sum(1 for g, _ in self.gc if g == 2)}


def _steal_ticks():
    """Ticks of CPU time the hypervisor stole from this machine, summed
    over its CPUs (``/proc/stat``); None where the file has no such
    field."""
    try:
        with open("/proc/stat") as fh:
            fields = fh.readline().split()
        return int(fields[8]) if fields[0] == "cpu" and len(fields) > 8 else None
    except OSError:
        return None


def log_stalls(intervals, waits, steps, host, top: int = 5) -> None:
    """The window's longest step intervals, each split into the wait for
    its batch and the step to its loss (host clock), and the host readings
    over the window."""
    if len(intervals):
        med = float(np.median(intervals))
        for i in np.argsort(intervals)[::-1][:top]:
            w = waits[i] if i < len(waits) else float("nan")
            s = steps[i] if i < len(steps) else float("nan")
            log(f"interval {i}: {1e3 * intervals[i]:.3f}ms (median "
                f"{1e3 * med:.3f}): wait {1e3 * w:.3f}ms, step "
                f"{1e3 * s:.3f}ms, rest {1e3 * (intervals[i] - w - s):.3f}ms")
    log("window host: " + ", ".join(
        f"{k}={v:.4f}" if isinstance(v, float) else f"{k}={v}"
        for k, v in host.items()))


def _profile_options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    return opts


def _check(config, parts, seed, pool, tables, kept, n_before, prog) -> dict:
    """Reference ETL over the pool and the model's reference over its own
    packed batches, compared with what the timed path produced."""
    ref_tables = parts.etl.fit(pool, config)
    got, want = [], []
    for (kind, i), b in sorted(kept.items()):
        j = (i if kind == "checked" else n_before + i) % len(pool)
        got.append(b)
        want.append(parts.etl.apply(pool[j], ref_tables, config))
    numbers = check.etl_numbers(tables, ref_tables, got, want)
    ref = parts.model_ref.train_steps(
        seed, parts.widths, config["optimizer"],
        [parts.etl.apply(pool[i], ref_tables, config)
         for i in range(CHECKED_STEPS)])
    numbers.update(check.step_numbers(prog, ref))
    return numbers


def control_numbers(config: dict, seed: int, pool: list) -> dict:
    """The control's numbers: the reference put in the program's place and
    computed in bfloat16, the precision below the configuration's float32,
    compared as a run's output is."""
    import ml_dtypes
    parts = Parts(config)
    tables = parts.etl.fit(pool, config)
    want = [parts.etl.apply(pool[i], tables, config)
            for i in range(CHECKED_STEPS)]
    got = [parts.etl.apply(pool[i], tables, config, ml_dtypes.bfloat16)
           for i in range(CHECKED_STEPS)]
    numbers = check.etl_numbers(tables, tables, got, want)
    o = config["optimizer"]
    ref = parts.model_ref.train_steps(seed, parts.widths, o, want)
    ctrl = parts.model_ref.train_steps(seed, parts.widths, o, got,
                                       dtype=jnp.bfloat16)
    numbers.update(check.step_numbers(ctrl, ref))
    return numbers
