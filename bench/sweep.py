#!/usr/bin/env python3
"""Find a score cell's knee: the highest offered rate it sustains.

    python3 bench/sweep.py --workload <score cell> --rates 1000,2000,4000 \
        --seconds 8 --seeds 11,12,13

One process builds the cell once, then serves a window at each rate on
each seed (a fresh runtime and the seed's own arrivals and ids each
time) and prints one JSON line per rate and seed: offered and completed
rate, ``score_p95_ms``, ``pacer_lag_p95_ms``, and the 95th percentile of
the window's first and last quarter.  A window sustains its rate when
as many requests complete in it as 98% of those due in it, and
the last quarter's tail is at most 1.5 times the first quarter's plus
5 ms: no backlog grows.  A rate is sustained when most of its seeds'
windows sustain it, so one stall on one seed neither sets nor lifts the
knee; the knee is the highest sustained rate.  The cell's mix runs at
0.8 of the knee, rounded to 100 per second; ``--write`` puts that rate
into the mix's file.  ``PERF.md`` records the sweep.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None, root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--write", action="store_true",
                    help="write 0.8 x the knee into the mix's file")
    args = ap.parse_args(argv)
    for p in (str(root / "src"), str(root)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax

    from bench import spec
    from bench.score import ScoreCell
    from bench.stats import percentile
    cell = spec.cell(args.workload, root)
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if cell.config.get("matmul_precision", "default") != "default":
        jax.config.update("jax_default_matmul_precision",
                          cell.config["matmul_precision"])
    rates = [float(r) for r in args.rates.split(",")]
    seeds = [int(x) for x in args.seeds.split(",")]
    held: dict[float, list[bool]] = {}
    impl = ScoreCell(cell.config, dict(cell.mix, rate=rates[0]), seeds[0])
    impl.setup(args.seconds)
    first = True
    for rate in rates:
        held[rate] = []
        for seed in seeds:
            if not first:
                impl.mix = dict(cell.mix, rate=rate)
                impl.seed = seed
                impl.draw(args.seconds)
            first = False
            impl.run(args.seconds)
            impl.close()
            row = _window(impl, rate, args.seconds, percentile)
            held[rate].append(row["sustained"])
            print(json.dumps(dict(workload=cell.name, seed=seed, **row)),
                  flush=True)
    knee = knee_of(held)
    if knee is None:
        print("no rate was sustained", file=sys.stderr)
        return 1
    rate = int(round(0.8 * knee, -2))
    print(json.dumps({"workload": cell.name, "knee_per_s": knee,
                      "rate_per_s": rate}), flush=True)
    if args.write:
        bm = spec.load_benchmark(root)
        name = next(w["traffic"] for w in bm["workloads"]
                    if w["name"] == cell.name)
        path = root / "bench" / "traffic" / f"{name}.json"
        mix = json.loads(path.read_text())
        mix["rate"] = rate
        path.write_text(json.dumps(mix, indent=2) + "\n")
    return 0


def knee_of(held: dict[float, list[bool]]) -> float | None:
    """The highest rate that most of its windows sustained."""
    ok = [r for r, w in held.items() if 2 * sum(w) > len(w)]
    return max(ok) if ok else None


def _window(impl, rate: float, seconds: float, percentile) -> dict:
    req, w = impl.req, impl.window_mask()
    lat = np.where(req.failed, np.inf, req.done - req.due)[w]
    q = (req.due[w] - impl.t0) / seconds
    p95_first = percentile(lat[q < 0.25].tolist(), 95) * 1e3
    p95_last = percentile(lat[q >= 0.75].tolist(), 95) * 1e3
    done_in = ((req.done >= impl.t0) & (req.done < impl.t1)).sum()
    completed = done_in / seconds
    return {"offered_per_s": rate, "due_per_s": float(w.sum() / seconds),
            "completed_per_s": float(completed),
            "score_p95_ms": percentile(lat.tolist(), 95) * 1e3,
            "p95_first_quarter_ms": p95_first,
            "p95_last_quarter_ms": p95_last,
            "pacer_lag_p95_ms": percentile(
                (req.sent[w] - req.due[w]).tolist(), 95) * 1e3,
            "batch_occupancy": impl.counters["avg_batch_occupancy"],
            "sustained": bool(done_in >= 0.98 * w.sum()
                              and p95_last <= 1.5 * p95_first + 5.0)}


if __name__ == "__main__":
    sys.exit(main())
