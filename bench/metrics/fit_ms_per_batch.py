"""Host-clock time of the set-up's timed ``job.fit()``, ended on the
fitted tables, per fitted batch."""


def read(run):
    c = run["counters"]
    if not c.get("fit_batches"):
        return None
    return 1e3 * c["fit_s"] / c["fit_batches"]
