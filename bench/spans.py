"""The program's own spans in a profile (``etl.*``, ``train.*``).

The program opens a ``jax.profiler`` span over each piece of host work it
accounts (``etl_runtime/runtime.py``: ``etl.read``, ``etl.transform``,
``etl.order``, ``etl.place``, ``etl.lookahead``, ``etl.deliver``;
``etl.fit`` per fit chunk; ``train.step`` per step), each with its
arguments (``batch``, ``bytes``, ``depth``, ``chunk``, ``step_num``).
This module reduces them, from the host planes alone, so that a trace
recorded on the CPU reduces as one recorded on the chip does:

- ``program_spans``: per span name, the total seconds and the count of
  the spans wholly inside the traced window (``bench.traced``; the whole
  trace where it has none), on any thread.  ``etl.transform`` also holds
  ``h2d_s``, the union of the runtime's host-to-device events
  (``H2D_EVENTS``, on any host thread) inside each transform span, summed
  over the spans, and ``h2d_window_s``, that union over the whole window;
  both are left out where the window holds no such event.
- ``idle_gap_spans``: for the idle gaps ``bench/trace.py`` lists, the
  program spans open across most of each gap, with their thread line.

Readers under ``bench/metrics/`` take the program spans of a run with
``for_run(run)``.
"""

from __future__ import annotations

import glob
import os
import tempfile

from bench import trace

PREFIXES = ("etl.", "train.")
TRANSFORM = "etl.transform"
H2D_EVENTS = frozenset({"XlaLinearize", "tpu::System::TransferToDevice"})


def _host_lines(planes):
    """(label, line) of every line of the host planes; the label is the
    line's name and its index in its plane, as Python threads share one
    name."""
    for plane in planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                yield f"{line.name}#{i}", line


def window(planes) -> tuple:
    """(start_ns, end_ns) of the traced window as ``bench/trace.py`` takes
    it, or of the whole host trace where it has no ``bench.traced``."""
    spans = trace._host_spans(planes)
    for name, s, e in spans:
        if name == trace.WINDOW_SPAN:
            return s, e
    ends = [(ev.start_ns, ev.start_ns + ev.duration_ns)
            for _, line in _host_lines(planes) for ev in line.events]
    if not ends:
        raise ValueError("trace holds no host event")
    return min(s for s, _ in ends), max(e for _, e in ends)


def program_events(planes, win=None) -> list:
    """Every program span wholly inside the window, as dicts with ``name``,
    ``start_ns``, ``end_ns``, ``line`` and ``args``, in start order."""
    planes = list(planes)
    w0, w1 = win or window(planes)
    out = []
    for label, line in _host_lines(planes):
        for ev in line.events:
            if not ev.name.startswith(PREFIXES):
                continue
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            if s >= w0 and e <= w1:
                out.append({"name": ev.name, "start_ns": s, "end_ns": e,
                            "line": label, "args": dict(ev.stats)})
    out.sort(key=lambda ev: ev["start_ns"])
    return out


def _h2d(planes, w0, w1) -> list:
    """Union of the runtime's host-to-device events, clipped to the
    window."""
    return trace._merge(
        (max(ev.start_ns, w0), min(ev.start_ns + ev.duration_ns, w1))
        for _, line in _host_lines(planes) for ev in line.events
        if ev.name in H2D_EVENTS
        and ev.start_ns < w1 and ev.start_ns + ev.duration_ns > w0)


def _overlap(intervals, s, e) -> int:
    return sum(max(0, min(e, ie) - max(s, is_)) for is_, ie in intervals)


def program_spans(planes) -> dict:
    """{span name: {"total_s", "count"}} over the traced window, with
    ``h2d_s`` and ``h2d_window_s`` on ``etl.transform`` (module
    docstring)."""
    planes = list(planes)
    w0, w1 = window(planes)
    events = program_events(planes, (w0, w1))
    out: dict = {}
    for ev in events:
        tot = out.setdefault(ev["name"], {"total_s": 0.0, "count": 0})
        tot["total_s"] += (ev["end_ns"] - ev["start_ns"]) / 1e9
        tot["count"] += 1
    h2d = _h2d(planes, w0, w1)
    if h2d and TRANSFORM in out:
        out[TRANSFORM]["h2d_s"] = sum(
            _overlap(h2d, ev["start_ns"], ev["end_ns"])
            for ev in events if ev["name"] == TRANSFORM) / 1e9
        out[TRANSFORM]["h2d_window_s"] = sum(e - s for s, e in h2d) / 1e9
    return out


def idle_gap_spans(planes, max_entries: int = 10) -> list:
    """The idle gaps of ``trace.breakdown(...)["idle_gaps"]``, longest
    first over the chips, each as ``[bench label, seconds, spans]``:
    ``spans`` lists ``[name, thread line, batch]`` (the step number for
    ``train.step``) for every program span open across more than half of
    the gap."""
    planes = list(planes)
    w0, w1 = window(planes)
    host = trace._host_spans(planes)
    events = program_events(planes, (w0, w1))
    gaps = []
    for plane in planes:
        if not trace._DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        src = lines.get("XLA Ops") or lines.get("XLA Modules") or []
        busy = trace._merge(
            (max(ev.start_ns, w0), min(ev.start_ns + ev.duration_ns, w1))
            for ev in src
            if ev.start_ns < w1 and ev.start_ns + ev.duration_ns > w0)
        busy = [iv for iv in busy if iv[1] > iv[0]]
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        gaps += sorted(((ge - gs, gs, ge) for gs, ge
                        in zip(edges[0::2], edges[1::2]) if ge > gs),
                       reverse=True)[:max_entries]
    out = []
    for d, gs, ge in sorted(gaps, reverse=True)[:max_entries]:
        held = [[ev["name"], ev["line"],
                 ev["args"].get("batch", ev["args"].get("step_num"))]
                for ev in events
                if 2 * _overlap([(ev["start_ns"], ev["end_ns"])], gs, ge) > d]
        out.append([trace._label_gap(gs, ge, host), d / 1e9, held])
    return out


def for_run(run) -> dict | None:
    """The program spans of a run's traced window: the trace reduction's
    ``program_spans`` where it has them; else those of the profile the
    harness recorded for the run, found under the temporary directory as
    the one whose traced window is the reduction's, and kept in the
    reduction under that key for the run's other readers.  None where
    there is no trace; empty where no profile matches."""
    tr = run.get("trace")
    if tr is None:
        return None
    if "program_spans" not in tr:
        tr["program_spans"] = _find(tr["window_s"])
    return tr["program_spans"]


def _find(window_s: float) -> dict:
    """Program spans of the newest profile under ``bench-*/trace`` in the
    temporary directory whose traced window lasts ``window_s``."""
    import jax
    pattern = os.path.join(tempfile.gettempdir(), "bench-*", "trace", "**",
                           "*.xplane.pb")
    found = []
    for path in glob.glob(pattern, recursive=True):
        try:
            found.append((os.path.getmtime(path), path))
        except OSError:
            continue
    for _, path in sorted(found, reverse=True):
        try:
            planes = list(jax.profiler.ProfileData.from_file(path).planes)
            w0, w1 = window(planes)
        except Exception:  # unreadable or empty: not this run's profile
            continue
        if (w1 - w0) / 1e9 == window_s:
            return program_spans(planes)
    return {}
