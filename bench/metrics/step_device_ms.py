"""Device time of the DLRM train-step program per execution, from the
trace; on several chips, the mean over the chips that ran it."""

MODULE = "jit_train_step"


def read(run):
    tr = run["trace"]
    if tr is None:
        return None
    per_chip = [chip["modules"][MODULE][0] / chip["modules"][MODULE][1]
                for chip in tr["chips"]
                if chip["modules"].get(MODULE, (0, 0))[1]]
    return 1e3 * sum(per_chip) / len(per_chip) if per_chip else None
