"""Reduction of a profiler trace (``.xplane.pb``) to what the readers use.

The traced window is the host span ``bench.traced`` that the harness
opens and closes around the part of the measured window it traces.  For
each device plane (``/device:TPU:<n>``) the reduction gives, clipped to
that window:

- busy intervals: the union of the plane's XLA op events;
- time and executions per XLA module (``jit_train_step``, ...), the
  suffix in parentheses dropped; an execution cut by the window's edge
  counts for the share of it inside;
- time in collective operations (all-reduce, all-gather, reduce-scatter,
  all-to-all, collective-permute, and their async start/done halves);
- time per op, named ``<module>/<instruction>``, for the breakdown;
- idle gaps, each labelled with the ``bench.*`` host span that covers
  most of it (``host`` where none does).

Nothing here knows a model or a pipeline; readers under
``bench/metrics/`` turn this into numbers.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

WINDOW_SPAN = "bench.traced"
SPAN_PREFIX = "bench."
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
_COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|allreduce|allgather|reducescatter", re.IGNORECASE)
_MODULE_SUFFIX = re.compile(r"\(\d+\)$")


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(s, e, w0, w1):
    return max(s, w0), min(e, w1)


def _host_spans(planes):
    """(name, start_ns, end_ns) of every ``bench.*`` span on host planes."""
    spans = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
    return spans


def _label_gap(s, e, spans):
    best, best_overlap = "host", 0.0
    for name, hs, he in spans:
        if name == WINDOW_SPAN:
            continue
        ov = min(e, he) - max(s, hs)
        if ov > best_overlap:
            best, best_overlap = name, ov
    return best


def reduce_planes(planes, max_entries: int = 10) -> dict:
    """Reduce already-loaded planes (``ProfileData(...).planes``)."""
    planes = list(planes)
    spans = _host_spans(planes)
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    w0, w1 = windows[0]
    span_stats: dict = {}
    for name, s, e in spans:
        if name == WINDOW_SPAN or s < w0 or e > w1:
            continue
        tot = span_stats.setdefault(name, [0.0, 0])
        tot[0] += (e - s) / 1e9
        tot[1] += 1
    chips = []
    for plane in planes:
        m = _DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        op_events = lines.get("XLA Ops") or []
        busy_src = op_events or lines.get("XLA Modules") or []
        busy = _merge(_clip(ev.start_ns, ev.start_ns + ev.duration_ns, w0, w1)
                      for ev in busy_src
                      if ev.start_ns < w1 and ev.start_ns + ev.duration_ns > w0)
        busy = [iv for iv in busy if iv[1] > iv[0]]
        modules: dict = {}
        spans_m = []
        for ev in lines.get("XLA Modules") or []:
            s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, w0, w1)
            if e <= s or ev.duration_ns <= 0:
                continue
            name = _MODULE_SUFFIX.sub("", ev.name)
            spans_m.append((ev.start_ns, ev.start_ns + ev.duration_ns, name))
            tot = modules.setdefault(name, [0.0, 0.0])
            tot[0] += (e - s) / 1e9
            tot[1] += (e - s) / ev.duration_ns
        spans_m.sort()
        starts = [m[0] for m in spans_m]
        ops: dict = {}
        collective = 0.0
        for ev in op_events:
            s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, w0, w1)
            if e <= s:
                continue
            i = bisect.bisect_right(starts, ev.start_ns) - 1
            mod = (spans_m[i][2] if i >= 0 and ev.start_ns < spans_m[i][1]
                   else "")
            op = ev.name.split(" = ", 1)[0].lstrip("%")
            key = f"{mod}/{op}"
            ops[key] = ops.get(key, 0.0) + (e - s) / 1e9
            if _COLLECTIVE.search(op):
                collective += (e - s) / 1e9
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        longest = sorted(((ge - gs, gs, ge) for gs, ge
                          in zip(edges[0::2], edges[1::2]) if ge > gs),
                         reverse=True)[:max_entries]
        gaps = [(_label_gap(gs, ge, spans), d / 1e9) for d, gs, ge in longest]
        chips.append({
            "plane": plane.name,
            "busy_s": sum(e - s for s, e in busy) / 1e9,
            "modules": modules,
            "collective_s": collective,
            "ops": ops,
            "gaps": gaps,
        })
    if not chips:
        raise ValueError("trace holds no device plane")
    return {"window_s": (w1 - w0) / 1e9, "spans": span_stats,
            "chips": chips}


def reduce_file(path: str, max_entries: int = 10) -> dict:
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    return reduce_planes(data.planes, max_entries=max_entries)


def breakdown(red: dict, max_entries: int = 10) -> dict:
    """The ``breakdown`` of a result line: the device ops with the most
    time (summed over chips) and the longest idle gaps (over chips), each
    labelled with the host span that covered it."""
    ops: dict = {}
    for chip in red["chips"]:
        for name, s in chip["ops"].items():
            ops[name] = ops.get(name, 0.0) + s
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:max_entries]
    gaps = sorted((g for chip in red["chips"] for g in chip["gaps"]),
                  key=lambda g: -g[1])[:max_entries]
    return {"device_ops": [[n, s] for n, s in top],
            "idle_gaps": [[n, s] for n, s in gaps]}
