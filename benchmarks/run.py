"""Benchmark entry point: the serving benchmark (BENCH_serve.json
artifact) + one section per paper table/figure + the kernel microbench +
the roofline table from the dry-run artifacts.

Every section now writes a ``BENCH_<name>.json`` artifact next to the
existing ``BENCH_serve.json`` (load, decode, table1, table2, fig2,
kernels, roofline), so CI can upload machine-readable results even when
a section partially fails — failures are recorded in the artifact
instead of lost in stdout.

Prints ``name,us_per_call,derived`` CSV rows (one per method x dataset).
Env: BENCH_FAST=0 for the full pass (fast is the default); BENCH_SKIP_TABLES=1
to only run serving + kernels + roofline summary; BENCH_OUT_DIR overrides
where the JSON artifacts land (default: cwd).
"""

from __future__ import annotations

import glob
import json
import os
import time

import jax
import jax.numpy as jnp


def _write_artifact(name: str, payload: dict) -> str:
    out_dir = os.environ.get("BENCH_OUT_DIR", ".")
    path = os.path.join(out_dir, f"BENCH_{name}.json")
    payload = {"bench": name, "backend": jax.default_backend(), **payload}
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {path}")
    return path


def bench_kernels() -> tuple[dict, list[str]]:
    """Pallas-kernel wrappers vs refs (CPU: interpret-mode correctness
    pass + ref-path timing; TPU timing is the deploy target), plus the
    lss_topk dedup-strategy C-sweep with its measured quadratic/bitonic
    crossover (the data behind the registry's auto-select threshold)."""
    from repro.kernels import bucket_logits, simhash_codes
    recs, rows = [], []
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (256, 128))
    theta = jax.random.normal(jax.random.PRNGKey(1), (128, 12))
    f = jax.jit(lambda q: simhash_codes(q, theta, 12, 1, impl="ref"))
    f(q)
    t0 = time.perf_counter()
    for _ in range(20):
        jax.block_until_ready(f(q))
    us = (time.perf_counter() - t0) / 20 / 256 * 1e6
    recs.append({"kernel": "simhash_codes", "impl": "ref",
                 "us_per_query": round(us, 3), "shape": "B256_d128_K12"})
    rows.append(f"kernel_simhash_codes_ref,{us:.3f},B256_d128_K12")

    w = jax.random.normal(jax.random.PRNGKey(2), (1024, 128, 128))
    ids = jax.random.randint(jax.random.PRNGKey(3), (256, 1), 0, 1024)
    g = jax.jit(lambda q, ids: bucket_logits(q, w, ids, impl="ref"))
    g(q, ids)
    t0 = time.perf_counter()
    for _ in range(20):
        jax.block_until_ready(g(q, ids))
    us = (time.perf_counter() - t0) / 20 / 256 * 1e6
    recs.append({"kernel": "bucket_logits", "impl": "ref",
                 "us_per_query": round(us, 3), "shape": "S1024_P128_d128"})
    rows.append(f"kernel_bucket_logits_ref,{us:.3f},S1024_P128_d128")

    # lss_topk dedup strategy C-sweep (quadratic vs bitonic, ref path).
    # BENCH_SKIP_DEDUP_SWEEP=1 skips it (CI's dedicated guard step runs
    # the sweep itself and MERGES into the same artifact, so the main
    # bench job doesn't pay for — or clobber — a second sweep).
    if os.environ.get("BENCH_SKIP_DEDUP_SWEEP"):
        return {"rows": recs, "crossover_c": None}, rows
    from benchmarks.kernels_bench import bench_dedup_sweep
    fast = os.environ.get("BENCH_FAST", "1") != "0"
    sweep = bench_dedup_sweep(cs=(512, 2048, 8192) if fast
                              else (512, 1024, 2048, 4096, 8192, 16384))
    recs.extend(sweep["rows"])
    for r in sweep["rows"]:
        rows.append(f"kernel_lss_topk_ref_{r['dedup']}_c{r['c']},"
                    f"{r['us_per_query']:.3f},{r['shape']}")
    rows.append(f"kernel_lss_topk_dedup_crossover,0,"
                f"crossover_c={sweep['crossover_c']}")

    # slab_dtype storage sweep: us/query, per-query slab DMA bytes, and
    # top-k label recall delta vs fp32, one synthetic-WOL index per format
    from benchmarks.kernels_bench import bench_slab_dtype_sweep
    slab = bench_slab_dtype_sweep()
    recs.extend(slab["rows"])
    for r in slab["rows"]:
        rows.append(f"kernel_lss_topk_ref_slab_{r['slab_dtype']},"
                    f"{r['us_per_query']:.3f},{r['shape']},"
                    f"dma={r['dma_bytes_per_query']},"
                    f"recall_delta={r['recall_delta_vs_fp32']:.4f}")
    return {"rows": recs, "crossover_c": sweep["crossover_c"]}, rows


def roofline_summary() -> tuple[list[dict], list[str]]:
    recs, rows = [], []
    for tag, pat in (("dryrun", "experiments/dryrun/*.json"),
                     ("dryrun_opt", "experiments/dryrun_opt/*.json")):
        for path in sorted(glob.glob(pat)):
            rec = json.load(open(path))
            r = rec["roofline"]
            recs.append({"tag": tag, "arch": rec["arch"],
                         "shape": rec["shape"], "mesh": rec["mesh"],
                         "roofline": r, "memory": rec["memory"]})
            rows.append(
                f"{tag}_{rec['arch']}_{rec['shape']}_{rec['mesh']},"
                f"{max(r['t_compute'], r['t_memory'], r['t_collective']) * 1e6:.1f},"
                f"bound={r['bottleneck']};useful={r['useful_ratio']:.2f};"
                f"mem_gb={rec['memory']['total_per_device_gb']}")
    return recs, rows


def bench_serving_rows() -> list[str]:
    """Unified-engine serving bench; writes BENCH_serve.json first so the
    artifact lands even if a later section is interrupted."""
    from benchmarks.serve_bench import bench_serving, write_artifact
    fast = os.environ.get("BENCH_FAST", "1") != "0"
    rec = bench_serving(fast=fast)
    write_artifact(rec)   # honors BENCH_SERVE_OUT / BENCH_OUT_DIR itself
    return [
        f"serve_m{r['m']}_{r['head']}_{r['impl']},{r['us_per_query']:.1f},"
        f"rps={r['req_per_s']};sample={r['avg_sample_size']:.0f};"
        f"speedup={r['speedup_vs_full']}"
        for r in rec["rows"]
    ]


def bench_load_rows() -> list[str]:
    """Short open-loop load run through the AsyncRuntime (one paced QPS
    point + one burst/saturation point); writes BENCH_load.json."""
    from benchmarks.load_bench import bench_load, write_artifact
    fast = os.environ.get("BENCH_FAST", "1") != "0"
    rec = bench_load(
        m=5_000 if fast else 50_000,
        n_requests=128 if fast else 1024,
        qps_list=[200.0, 0.0],
        heads=["lss"], impls=["ref"],
        buckets=(1, 4, 16), policy="shed", max_queue=4096,
        deadline_ms=None)
    write_artifact(rec)   # honors BENCH_LOAD_OUT / BENCH_OUT_DIR itself
    return [
        f"load_{r['head']}_{r['impl']}_"
        f"{'burst' if r['qps'] <= 0 else 'qps%g' % r['qps']},"
        f"{r['p50_ms']:.2f},"
        f"rps={r['achieved_rps']};p99={r['p99_ms']};occ={r['occupancy']};"
        f"shed={r['shed_queue']}+{r['shed_deadline']};"
        f"speedup_vs_sync={r['speedup_vs_sync']}"
        for r in rec["rows"]
    ]


def bench_obs_rows() -> list[str]:
    """Observability tax + online-vs-offline audit recall agreement;
    writes BENCH_obs.json (the obs CI job re-runs this with the
    overhead guard armed)."""
    from benchmarks.load_bench import bench_obs, write_artifact
    fast = os.environ.get("BENCH_FAST", "1") != "0"
    rec = bench_obs(
        m=5_000 if fast else 50_000,
        n_requests=128 if fast else 1024,
        buckets=(1, 4, 16), audit_rate=0.05,
        reps=2 if fast else 3)
    write_artifact(rec)   # honors BENCH_OBS_OUT / BENCH_OUT_DIR itself
    oh = next(r for r in rec["rows"] if r["kind"] == "overhead")
    au = next(r for r in rec["rows"] if r["kind"] == "audit_recall")
    return [
        f"obs_overhead,{oh['overhead_pct']:.2f},"
        f"rps_on={oh['rps_on']};rps_off={oh['rps_off']};"
        f"p99_on={oh['p99_on_ms']};p99_off={oh['p99_off_ms']}",
        f"obs_audit_recall,{au['recall_online']:.6f},"
        f"offline={au['recall_offline']:.6f};delta={au['recall_delta']:.2e};"
        f"rows={au['n_rows']}",
    ]


def bench_decode_rows() -> list[str]:
    """Short streaming-decode load run (burst session arrivals, stream
    sweep, blocking per-prompt generate baseline); writes
    BENCH_decode.json."""
    from benchmarks.decode_bench import bench_decode, write_artifact
    fast = os.environ.get("BENCH_FAST", "1") != "0"
    rec = bench_decode(
        vocab=2048 if fast else 8192,
        n_sessions=8 if fast else 32,
        streams_list=[1, 2, 4] if fast else [1, 2, 4, 8],
        qps_list=[0.0], heads=["lss"],
        max_new_tokens=8 if fast else 32,
        impl="ref", max_queue=4096, deadline_ms=None)
    write_artifact(rec)   # honors BENCH_DECODE_OUT / BENCH_OUT_DIR itself
    return [
        f"decode_{r['head']}_s{r['streams']}_"
        f"{'burst' if r['qps'] <= 0 else 'qps%g' % r['qps']},"
        f"{r['ttft_p50_ms']:.2f},"
        f"tok_s={r['tokens_per_s']};itl_p50={r['itl_p50_ms']};"
        f"occ={r['occupancy']};shed={r['shed_queue']}+{r['shed_deadline']};"
        f"speedup_vs_blocking={r['speedup_vs_blocking']}"
        for r in rec["rows"]
    ]


def bench_tables(rows: list[str]) -> None:
    from benchmarks.paper_tables import (fig2_collision_curves,
                                         run_setting, table2_kl_sweep)
    # Table 1 (4 datasets x 5 methods)
    t1_rows, t1_failures = [], {}
    for name in ("wiki10-31k", "delicious-200k", "text8",
                 "wiki-text-2"):
        try:
            for r in run_setting(name):
                t1_rows.append(r._asdict())
                rows.append(
                    f"table1_{r.dataset}_{r.method},"
                    f"{r.us_per_query:.1f},"
                    f"P@1={r.p1:.4f};P@5={r.p5:.4f};"
                    f"recall={r.recall:.3f};sample={r.sample:.0f};"
                    f"mflop={r.mflop_per_query:.2f}")
        except Exception as e:   # keep the harness running
            t1_failures[name] = repr(e)
            rows.append(f"table1_{name}_FAILED,0,{e!r}")
    _write_artifact("table1", {"rows": t1_rows, "failures": t1_failures})
    # Table 2 (K x L sweep)
    try:
        t2 = table2_kl_sweep()
        _write_artifact("table2", {"rows": t2})
        for r in t2:
            rows.append(f"table2_K{r['K']}_L{r['L']},0,"
                        f"P@1={r['P@1']};P@5={r['P@5']};"
                        f"sample={r['sample']}")
    except Exception as e:
        _write_artifact("table2", {"rows": [], "failures": {"sweep": repr(e)}})
        rows.append(f"table2_FAILED,0,{e!r}")
    # Figure 2 (collision curves)
    try:
        hist = fig2_collision_curves()
        _write_artifact("fig2", {"curves": {
            k: list(map(float, v)) for k, v in hist.items()}})
        rows.append(
            "fig2_collision,0,"
            f"pos={[round(x, 3) for x in hist['p_collide_pos']]};"
            f"neg={[round(x, 3) for x in hist['p_collide_neg']]};"
            f"recall={[round(x, 3) for x in hist['recall']]}")
    except Exception as e:
        _write_artifact("fig2", {"curves": {}, "failures": {"fig2": repr(e)}})
        rows.append(f"fig2_FAILED,0,{e!r}")


def main() -> None:
    from repro.utils.compile_cache import use_compile_cache
    use_compile_cache()
    rows = []
    rows += bench_serving_rows()
    rows += bench_load_rows()
    rows += bench_obs_rows()
    rows += bench_decode_rows()
    kern_rec, kern_rows = bench_kernels()
    _write_artifact("kernels", kern_rec)
    rows += kern_rows
    if not os.environ.get("BENCH_SKIP_TABLES"):
        bench_tables(rows)
    roof_recs, roof_rows = roofline_summary()
    _write_artifact("roofline", {"rows": roof_recs})
    rows += roof_rows
    print("name,us_per_call,derived")
    for r in rows:
        print(r)


if __name__ == "__main__":
    main()
