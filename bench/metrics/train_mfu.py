"""Model FLOP utilization of training: the DLRM forward and backward
operations per row (``bench/work.py``, from the configuration's widths)
times the rows per second that the traced window completed, over the
chips' bf16 peak."""


def read(run):
    tr = run["trace"]
    if tr is None or not run["counters"].get("traced_steps"):
        return None
    rows_per_s = (run["counters"]["traced_steps"] * run["work"]["rows_per_step"]
                  / tr["window_s"])
    peak = run["chips"] * run["peaks"]["bf16_flops_per_s"]
    return 100.0 * run["work"]["train_flops_per_row"] * rows_per_s / peak
