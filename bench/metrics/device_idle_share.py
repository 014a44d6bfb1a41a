"""Share of the traced window in which no operation ran on the device:
one minus the union of busy intervals over the window, mean over the
chips."""


def read(run):
    tr = run["trace"]
    if tr is None or not tr["window_s"]:
        return None
    busy = sum(chip["busy_s"] for chip in tr["chips"]) / len(tr["chips"])
    return 100.0 * (1.0 - busy / tr["window_s"])
