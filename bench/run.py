"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Needs the chips the cell asks for: it exits non-zero, printing no result,
when JAX finds no TPU or too few of them.  The last line of standard output
is one JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``); its last key,
``checks``, holds every number the output check compared beside its
limit, and the same numbers close standard error.  Set-up phases, lowering
paths, kernel counts, peak memory and in-window compiles go to standard
error before them.
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


def require_chips(chips: int) -> None:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"[bench] JAX found no TPU (platform "
                         f"{devs[0].platform!r}); no result")
    if len(devs) < chips:
        raise SystemExit(f"[bench] the cell needs {chips} chips, JAX found "
                         f"{len(devs)}; no result")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    bench = harness.load_benchmark(_ROOT)
    cell, config, mix = harness.load_cell(bench, args.workload, _ROOT)
    require_chips(cell["chips"])
    result = harness.run_cell(bench, args.workload, args.seed, args.seconds,
                              bool(args.trace), t_process=T_PROCESS,
                              cell=cell, config=config, mix=mix)
    for name, c in result["checks"].items():
        print(f"[check] {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
