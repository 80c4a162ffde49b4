import os

from repro.xla_env import force_host_device_count

force_host_device_count(512)

"""Multi-pod dry-run: .lower().compile() every (arch x shape x mesh) cell.

The ONLY entry point that fakes 512 devices (set above, before any jax
import; user-set XLA_FLAGS are preserved, not clobbered).  Produces one JSON record per cell under --out with:
memory_analysis (bytes/device), cost_analysis (FLOPs, bytes), the parsed
collective schedule, and the three roofline terms.

Usage:
  python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
  python -m repro.launch.dryrun --arch all --mesh both --out experiments
"""

import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402

from repro.configs.registry import all_cells, get_config       # noqa: E402
from repro.launch.mesh import make_production_mesh             # noqa: E402
from repro.launch.roofline import parse_collectives, \
    roofline_from_terms                                        # noqa: E402
from repro.launch.steps import build_cell                      # noqa: E402


def _compile_cell(cell, mesh):
    donate = (0,) if cell.donate_state else ()
    jitted = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                     donate_argnums=donate)
    return jitted.lower(*cell.args).compile()


def _measure(compiled, cell, n_dev) -> dict:
    """Per-device corrected (flops, bytes, collective bytes)."""
    cost = compiled.cost_analysis()
    coll = parse_collectives(compiled.as_text(), n_dev)
    return {
        "flops": float(cost.get("flops", 0.0))
        + cell.flops_correction / n_dev,
        "bytes": float(cost.get("bytes accessed", 0.0))
        + cell.flops_correction / n_dev / 100.0,
        "coll_bytes": coll.total_bytes,
        "coll_by_op": coll.bytes_by_op,
        "coll_counts": coll.count_by_op,
    }


def _mem_record(compiled) -> dict:
    mem = compiled.memory_analysis()
    return {
        "argument_bytes": mem.argument_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "alias_bytes": mem.alias_size_in_bytes,
        "total_per_device_gb": round(
            (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes) / 2**30, 3),
    }


def run_cell(arch_id: str, shape_name: str, multi_pod: bool,
             out_dir: str | None) -> dict:
    """LM cells compile three ways: full depth w/ scan-over-layers (the
    production graph — this is the pass/fail + memory-fit proof) and
    unrolled at 2 & 4 layers, whose per-layer cost slope extrapolates
    exact FLOP/byte/collective counts to full depth (XLA cost_analysis
    ignores scan trip counts — measured, see EXPERIMENTS.md §Method).
    Non-LM cells have no layer stack and compile once."""
    from repro.configs.registry import get_config
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size
    t0 = time.time()
    rec = {"arch": arch_id, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16"}
    spec = get_config(arch_id)
    with jax.set_mesh(mesh):
        if spec.family == "lm":
            full_l = spec.model_cfg.n_layers
            cell = build_cell(arch_id, shape_name, mesh, lm_impl="scan")
            compiled = _compile_cell(cell, mesh)       # production proof
            rec["memory"] = _mem_record(compiled)
            c2 = build_cell(arch_id, shape_name, mesh, lm_layers=2)
            m2 = _measure(_compile_cell(c2, mesh), c2, n_dev)
            c4 = build_cell(arch_id, shape_name, mesh, lm_layers=4)
            m4 = _measure(_compile_cell(c4, mesh), c4, n_dev)
            meas = {}
            for k in ("flops", "bytes", "coll_bytes"):
                slope = (m4[k] - m2[k]) / 2.0
                meas[k] = m2[k] + slope * (full_l - 2)
            meas["coll_by_op"] = {
                k: m2["coll_by_op"].get(k, 0.0)
                + (m4["coll_by_op"].get(k, 0.0)
                   - m2["coll_by_op"].get(k, 0.0)) / 2.0 * (full_l - 2)
                for k in set(m2["coll_by_op"]) | set(m4["coll_by_op"])}
            meas["coll_counts"] = m4["coll_counts"]
            rec["method"] = "scan-proof + unrolled L2/L4 extrapolation"
        else:
            cell = build_cell(arch_id, shape_name, mesh)
            compiled = _compile_cell(cell, mesh)
            rec["memory"] = _mem_record(compiled)
            meas = _measure(compiled, cell, n_dev)
            rec["method"] = "direct"

        rec["cost"] = {"flops": meas["flops"],
                       "bytes_accessed": meas["bytes"]}
        rec["collectives"] = {
            "bytes_by_op": meas["coll_by_op"],
            "count_by_op": meas["coll_counts"],
            "total_bytes_per_device": meas["coll_bytes"],
        }
        roof = roofline_from_terms(meas["flops"], meas["bytes"],
                                   meas["coll_bytes"], n_dev,
                                   cell.model_flops)
        rec["roofline"] = roof.as_dict()
        rec["timings"] = {"total_s": round(time.time() - t0, 1)}
        rec["comment"] = cell.comment
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = f"{arch_id}_{shape_name}_{rec['mesh'].replace('x','_')}"
        with open(os.path.join(out_dir, f"{tag}.json"), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    cells = all_cells()
    if args.arch != "all":
        cells = [(a, s) for a, s in cells if a == args.arch]
    if args.shape != "all":
        cells = [(a, s) for a, s in cells if s == args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    failures = []
    for arch_id, shape_name in cells:
        for mp in meshes:
            tag = f"{arch_id}/{shape_name}/{'multi' if mp else 'single'}"
            try:
                rec = run_cell(arch_id, shape_name, mp, args.out)
                r = rec["roofline"]
                print(f"[dryrun] OK  {tag}: "
                      f"mem={rec['memory']['total_per_device_gb']}GB "
                      f"t_comp={r['t_compute']:.2e}s "
                      f"t_mem={r['t_memory']:.2e}s "
                      f"t_coll={r['t_collective']:.2e}s "
                      f"bound={r['bottleneck']} "
                      f"useful={r['useful_ratio']:.2f} "
                      f"({rec['timings']['total_s']}s)",
                      flush=True)
            except Exception as e:
                failures.append((tag, repr(e)))
                print(f"[dryrun] FAIL {tag}: {e!r}", flush=True)
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for t, e in failures:
            print(" ", t, e)
        raise SystemExit(1)
    print(f"\nall {len(cells) * len(meshes)} cells OK")


if __name__ == "__main__":
    main()
