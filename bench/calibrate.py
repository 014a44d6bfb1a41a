"""Readings that the output check's limits are set from, on the chip.

    python3 bench/calibrate.py --workload <name> --seeds <n> [--first-seed S]

For a dozen seeds or more, the numbers a sound run of the cell compares
(a short window each); on three of them, the numbers of the control (the
reference in bfloat16 in the program's place) and of each fault of
``bench/faults.py`` that needs a run.  All in one process, so the set-up
compiles once.  One JSON line per reading on standard output; the limits
themselves are written into the configuration file by hand, with the
readings in PERF.md.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(_ROOT, "src"), _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# a fault that reads 1 by the measure itself (an unchanged state has a
# zero first gradient and no change) needs no run
RUN_FAULTS = ("half_batch", "altered_answer")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=3_100_000_001)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--faults", type=int, default=3,
                    help="seeds for the control and each fault")
    ap.add_argument("--plant", nargs="*", default=None,
                    help="faults to run (default: every one the cell can "
                         "have that needs a run)")
    args = ap.parse_args(argv)

    from bench import faults, gen, harness
    from bench.run import require_chips
    bench = harness.load_benchmark(_ROOT)
    cell, config, mix = harness.load_cell(bench, args.workload, _ROOT)
    require_chips(cell["chips"])
    seeds = [args.first_seed + 7919 * i
             for i in range(max(args.seeds, args.faults))]
    planted = RUN_FAULTS if args.plant is None else tuple(args.plant)

    def emit(kind, seed, numbers):
        numbers = {k: v for k, v in numbers.items() if k != "dead_leaves"}
        print(json.dumps({"kind": kind, "seed": seed, **numbers}),
              flush=True)

    def run(seed):
        res = harness.run_cell(bench, args.workload, seed, args.seconds,
                               False, t_process=time.perf_counter(),
                               cell=cell, config=config, mix=mix)
        return {k: c["value"] for k, c in res["checks"].items()}

    for seed in seeds[:args.seeds]:
        emit("program", seed, run(seed))
    for seed in seeds[:args.faults]:
        emit("control", seed, harness.control_numbers(
            config, seed, gen.gen_pool(mix, seed)))
        for name in planted:
            with faults.FAULTS[name]():
                emit(name, seed, run(seed))


if __name__ == "__main__":
    main()
