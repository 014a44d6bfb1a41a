"""Mean host time of the executor's transform stage per batch: the
program's ``etl.transform`` spans (one raw batch handed to the apply
program: its arguments copied to the device and the program dispatched)
in the traced window."""

from bench import spans


def read(run):
    s = (spans.for_run(run) or {}).get(spans.TRANSFORM)
    return 1e3 * s["total_s"] / s["count"] if s else None
