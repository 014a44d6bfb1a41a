import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and extract the roofline inputs.

The two lines above MUST precede every other import: jax locks the device
count at first initialization, and the dry-run needs 512 placeholder host
devices to build the 2x16x16 multi-pod mesh.  (Do not set this globally —
smoke tests and benchmarks run on 1 device.)

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3_405b \
        --shape train_4k [--multi-pod]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

Each cell writes experiments/dryrun/<arch>__<shape>__<mesh>.json with
memory_analysis, cost_analysis, the collective inventory parsed from the
post-SPMD HLO, and the three roofline terms.  Results are cached: finished
cells are skipped unless --force.
"""

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402

from repro.configs.base import ALL_SHAPES  # noqa: E402
from repro.configs.registry import ARCH_IDS, canonical  # noqa: E402
from repro.distributed import hlo_analysis, hlo_cost  # noqa: E402
from repro.distributed.sharding import set_active_mesh  # noqa: E402
from repro.launch.cells import iter_cells, plan_cell  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402

SHAPES = {s.name: s for s in ALL_SHAPES}


def _mem_dict(mem) -> dict:
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        v = getattr(mem, k, None)
        if v is not None:
            out[k] = int(v)
    # peak live bytes per device (arguments alias outputs via donation)
    out["per_device_bytes"] = (out.get("argument_size_in_bytes", 0)
                               + out.get("temp_size_in_bytes", 0)
                               + out.get("output_size_in_bytes", 0)
                               - out.get("alias_size_in_bytes", 0))
    return out


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             out_dir: str = "experiments/dryrun", force: bool = False,
             tcfg=None, tag: str = "", verbose: bool = True) -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cell_id = f"{canonical(arch)}__{shape_name}__{mesh_name}" + (
        f"__{tag}" if tag else "")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, cell_id + ".json")
    if os.path.exists(path) and not force:
        with open(path) as fh:
            return json.load(fh)

    mesh = make_production_mesh(multi_pod=multi_pod)
    set_active_mesh(mesh)
    rec = {"cell": cell_id, "arch": canonical(arch), "shape": shape_name,
           "mesh": list(mesh.devices.shape), "chips": int(mesh.devices.size),
           "ok": False}
    try:
        shape = SHAPES[shape_name]
        t0 = time.perf_counter()
        plan = plan_cell(arch, shape, mesh, tcfg=tcfg)
        with mesh:
            lowered = plan.jitted.lower(*plan.abstract_args)
            rec["lower_s"] = round(time.perf_counter() - t0, 2)
            t1 = time.perf_counter()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.perf_counter() - t1, 2)

            mem = compiled.memory_analysis()
            rec["memory"] = _mem_dict(mem)
            xla_cost = compiled.cost_analysis()
            rec["xla_cost_analysis"] = {
                k: float(v) for k, v in xla_cost.items()
                if isinstance(v, (int, float)) and
                k in ("flops", "bytes accessed", "transcendentals")}
            # XLA's cost_analysis counts while bodies ONCE (verified); use the
            # trip-count-aware analyzer for the real roofline inputs.
            cost = hlo_cost.analyze(compiled.as_text())
            rec["cost"] = {"flops": cost["flops"],
                           "transcendentals": cost["transcendentals"],
                           "bytes_accessed": cost["bytes_accessed"]}
            flops = cost["flops"]
            hbm_bytes = cost["bytes_accessed"]
            rec["collectives"] = {
                "per_op": cost["per_op"],
                "collective_bytes": cost["collective_bytes"],
                "wire_bytes": cost["wire_bytes"],
                "n_collectives": cost["n_collectives"]}
            rec["model_flops"] = plan.model_flops
            # the analyzed module is per-device post-SPMD: model_flops is
            # global — normalize for the useful-compute ratio
            per_dev_model_flops = plan.model_flops / rec["chips"]
            rec["hlo_vs_model_flops"] = (
                flops / per_dev_model_flops if per_dev_model_flops else None)
            rec["roofline"] = hlo_analysis.roofline_terms(
                flops, hbm_bytes, cost["collective_bytes"],
                cost["wire_bytes"], rec["chips"])
            rec["ok"] = True
    except Exception as e:  # record failures — they are bugs to fix
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    finally:
        set_active_mesh(None)

    with open(path, "w") as fh:
        json.dump(rec, fh, indent=1)
    if verbose:
        if rec["ok"]:
            r = rec["roofline"]
            print(f"[dryrun] {cell_id}: OK compile={rec['compile_s']}s "
                  f"mem/dev={rec['memory']['per_device_bytes']/2**30:.2f}GiB "
                  f"compute={r['t_compute_s']:.4f}s memory={r['t_memory_s']:.4f}s "
                  f"wire={r['t_wire_s']:.4f}s dominant={r['dominant']}",
                  flush=True)
        else:
            print(f"[dryrun] {cell_id}: FAIL {rec['error']}", flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    todo = []
    if args.all:
        for arch, shape, skip in iter_cells():
            if skip:
                print(f"[dryrun] SKIP {arch}__{shape.name}: {skip}")
                continue
            todo.append((arch, shape.name))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        todo.append((args.arch, args.shape))

    failures = 0
    for mp in meshes:
        for arch, shape in todo:
            rec = run_cell(arch, shape, multi_pod=mp, out_dir=args.out,
                           force=args.force)
            failures += 0 if rec["ok"] else 1
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()