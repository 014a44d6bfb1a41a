"""Mean host time of the executor's read stage per batch: the program's
``etl.read`` spans (``next(source)`` and the envelope wrap: one raw batch
read and decoded) in the traced window."""

from bench import spans


def read(run):
    s = (spans.for_run(run) or {}).get("etl.read")
    return 1e3 * s["total_s"] / s["count"] if s else None
