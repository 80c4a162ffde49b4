#!/usr/bin/env python3
"""Readings of a cell's compared numbers for the program and for its
control, on many seeds in one process, at the cell's own size and load.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 4

For each seed the cell is built and served for a short window as a run
serves it; the sample of what the window served is read against the
reference (``program``) and the reference at the next precision below
the configuration's, put in the program's place, is read the same way
(``control``).  One JSON line per seed on standard output.  The limits
in ``bench/limits/`` are set between the program's largest reading and
the control's smallest; ``PERF.md`` records both.

The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

# the next precision below each configuration's
CONTROL = {"bfloat16": "fp8", "float32": "high"}


def main(argv=None, root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args(argv)
    for p in (str(root / "src"), str(root)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax

    from bench import check, spec
    from bench.lm_decode import DecodeCell
    from bench.score import ScoreCell
    cell = spec.cell(args.workload, root)
    cfg, mix = cell.config, cell.mix
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if cfg.get("matmul_precision", "default") != "default":
        jax.config.update("jax_default_matmul_precision",
                          cfg["matmul_precision"])
    dtype = cfg.get("torch_dtype") or cfg.get("dtype")
    control = CONTROL[dtype]
    for seed in (int(s) for s in args.seeds.split(",")):
        impl = (DecodeCell if mix["kind"] == "decode" else ScoreCell)(
            cfg, mix, seed)
        impl.setup(args.seconds)
        impl.run(args.seconds)
        impl.close()
        served = check.samples(impl, mix)
        impl.free()
        gc.collect()
        program = check.compare(impl.params, seed, cfg, mix, served)
        ctrl = check.compare(impl.params, seed, cfg, mix, served,
                             control=control)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "control": control, "program": program,
                          "control_readings": ctrl}), flush=True)
        del impl
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
