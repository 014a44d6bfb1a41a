"""Host-to-device transfer time per batch: the union of the runtime's
host-to-device events (``bench/spans.py``'s ``H2D_EVENTS``) inside the
program's ``etl.transform`` spans of the traced window, over the count of
those spans."""

from bench import spans


def read(run):
    s = (spans.for_run(run) or {}).get(spans.TRANSFORM)
    if not s or "h2d_s" not in s:
        return None
    return 1e3 * s["h2d_s"] / s["count"]
