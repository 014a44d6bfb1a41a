"""Host-clock time the trainer waited on the executor for its next batch
(``RuntimeStats.consumer_wait_s``), per step of the measured window."""


def read(run):
    c = run["counters"]
    if not c.get("window_steps"):
        return None
    return 1e3 * c["trainer_wait_s"] / c["window_steps"]
