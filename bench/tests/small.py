"""A cell cut to a size the CPU runs in seconds, for the check's tests.

    python -m bench.tests.small <workload> <case> [<case> ...]

runs the cell once per case ("sound", or a fault of ``bench/faults.py``)
and prints one JSON line of {case: correct}; a test runs it in a child
process from a copy of the benchmark with files added.
"""

from __future__ import annotations

import copy
import json
import sys
import time

ROWS, VOCAB, SEED = 256, 512, 2 ** 33 + 5


def small(config: dict, mix: dict) -> tuple:
    config, mix = copy.deepcopy(config), copy.deepcopy(mix)
    kw = config["pipeline"]["paper_pipeline"]
    kw["batch_size"] = ROWS
    for key in ("modulus", "small_vocab", "large_vocab"):
        if key in kw:
            kw[key] = VOCAB
    config["max_ind_range"] = VOCAB
    mix["batch_rows"] = ROWS
    mix["pool_batches"] = 4
    return config, mix


def run(workload: str, fault=None) -> dict:
    import contextlib
    from bench import harness
    bench = harness.load_benchmark()
    cell, config, mix = harness.load_cell(bench, workload)
    config, mix = small(config, mix)
    with fault() if fault else contextlib.nullcontext():
        return harness.run_cell(bench, workload, SEED, 1.0, False,
                                t_process=time.perf_counter(), cell=cell,
                                config=config, mix=mix)


def main(argv) -> None:
    from bench import faults
    workload, cases = argv[0], argv[1:]
    out = {case: run(workload, faults.FAULTS.get(case))["correct"]
           for case in cases}
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
