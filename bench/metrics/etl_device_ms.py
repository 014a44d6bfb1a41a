"""Device time of the ETL apply program per execution, from the trace.

The apply program is the XLA module of the compiled pipeline's jitted
apply function; it runs once per delivered batch, on one chip.
"""

MODULE = "jit_apply_fn"


def read(run):
    tr = run["trace"]
    if tr is None:
        return None
    total, count = 0.0, 0
    for chip in tr["chips"]:
        s, n = chip["modules"].get(MODULE, (0.0, 0))
        total, count = total + s, count + n
    return 1e3 * total / count if count else None
