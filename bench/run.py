#!/usr/bin/env python3
"""Run one benchmark cell on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell (``BENCHMARK.json``'s
``workloads``) names a configuration and a traffic mix; the run

1. refuses an unknown cell, a language model whose ``model_type`` has
   no ``bench/models/<model_type>.py``, any device that is not a TPU
   with published peaks (``bench/peaks.py``), and a host with fewer
   chips than the cell asks for: exit code 2, no result line;
2. builds the cell from the seed, on the device: weights, index, the
   program's server, every request of the run;
3. warms exactly the shapes the mix uses, then pre-rolls its traffic;
4. measures for ``--seconds`` (with ``--trace 1``, records the last
   ``TRACE_S`` seconds of the window, or its last half if shorter, with
   the profiler, its Python tracer off; per-layer metrics read on the
   host clock cover the window up to where the profiler starts);
5. once the window has closed and the program's state is freed, compares
   a sample of what the timed path served with the plain reference
   (``bench/check.py``), against the cell's limits (``bench/limits/``);
6. prints the result as the last line of standard output: one JSON
   object with ``correct``, ``attempted``, ``failed``, ``metrics``,
   ``device``, with ``--trace 1`` also ``breakdown``, and last
   ``checks``: each compared number beside its limit (also the last
   lines of standard error).

``setup_s`` runs from the start of this process to the window's first
timed request: loading, weights, index, compiling or loading compiled
programs, warm-up and pre-roll.  JAX's persistent compilation cache is
``$JAX_COMPILATION_CACHE_DIR`` where that is set, else ``.jax_cache/``
in the checkout, with every program cached.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
TRACE_S = 2.0            # the profiled slice at the end of a --trace 1 window


def _fail(msg: str, code: int = 2) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return code


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


class _Compiles:
    """Host times of compiles and compile-cache loads (JAX's monitoring
    events), to count those inside the window."""

    def __init__(self, jax):
        self.times: list[float] = []

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.times.append(time.perf_counter())

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.times.append(time.perf_counter())

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.times if t0 <= t < t1)


def _device(jax, chips: int, require_chip: bool):
    devs = jax.devices()
    kind = devs[0].device_kind
    if require_chip:
        from bench.peaks import PEAKS
        if devs[0].platform != "tpu" or kind not in PEAKS:
            raise RuntimeError(f"no accelerator with published peaks: JAX "
                               f"finds {devs[0].platform} ({kind})")
        if len(devs) < chips:
            raise RuntimeError(f"the cell needs {chips} chips, JAX finds "
                               f"{len(devs)}")
    return devs


def main(argv=None, require_chip: bool = True, root: Path = ROOT) -> int:
    args = _parse(argv)
    if not (root / "src" / "repro").is_dir():
        return _fail(f"{root} holds no program (src/repro): nothing to run")
    for p in (str(root / "src"), str(root)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import check, spec
    try:
        cell = spec.cell(args.workload, root)
    except (KeyError, FileNotFoundError) as e:
        return _fail(str(e))
    cfg, mix = cell.config, cell.mix

    import jax
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if cfg.get("matmul_precision", "default") != "default":
        jax.config.update("jax_default_matmul_precision",
                          cfg["matmul_precision"])
    try:
        devs = _device(jax, cell.chips, require_chip)
    except RuntimeError as e:
        return _fail(str(e))
    compiles = _Compiles(jax)

    from bench.lm_decode import DecodeCell
    from bench.score import ScoreCell
    impl = (DecodeCell if mix["kind"] == "decode" else ScoreCell)(
        cfg, mix, args.seed)
    impl.setup(args.seconds)

    tr = SimpleNamespace(events=None, t_a=None, t_b=None, dir=None,
                         steps=None)

    def on_window(t0, t1):
        if not args.trace:
            return
        start = max(t1 - TRACE_S, (t0 + t1) / 2)
        time.sleep(max(0.0, start - time.perf_counter()))
        impl.mark_host_end()
        tr.dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tr.dir, profiler_options=opts)
        tr.t_a = time.perf_counter()
        steps_a = impl.steps_done()
        time.sleep(max(0.0, t1 - time.perf_counter()))
        tr.steps = impl.steps_done() - steps_a
        tr.t_b = time.perf_counter()
        jax.profiler.stop_trace()

    impl.run(args.seconds, on_window)
    setup_s = impl.t0 - T_START
    e2e = impl.end_to_end()
    attempted, failed = impl.attempted_failed()
    in_window = compiles.between(impl.t0, impl.t1)
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs[:cell.chips])
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": cell.chips, "memory_peak_bytes": peak}

    metrics = {}
    breakdown = None
    if args.trace:
        from bench import peaks, trace, work
        try:
            tr.events = trace.load_events(trace.xplane_file(tr.dir))
        finally:
            shutil.rmtree(tr.dir, ignore_errors=True)
        window_s = tr.t_b - tr.t_a
        busy = trace.busy_s(tr.events)
        device.update(busy_s=busy, window_s=window_s)
        pk = peaks.peaks_for(devs[0].device_kind)
        run = SimpleNamespace(
            cell=cell, impl=impl, trace=tr, busy_s=busy, window_s=window_s,
            work=work, peaks=pk, trace_mod=trace,
            work_floor=lambda w: peaks.floor_time_s(w.flops, w.nbytes, pk))
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"], root)(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = {"device_ops": trace.top_device_ops(tr.events),
                     "idle_gaps": trace.idle_gaps(tr.events)}
    else:
        for m in cell.end_to_end:
            v = setup_s if m["name"] == "setup_s" else e2e.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    impl.close()
    samples = check.samples(impl, mix)
    impl.free()
    gc.collect()
    numbers = check.compare(impl.params, impl.seed, cfg, mix, samples)

    checks = {}
    for name, lim in cell.limits["numbers"].items():
        v = numbers.get(name, math.inf)
        checks[name] = {"value": v, "limit": lim["limit"]}
    correct = (attempted > 0 and failed == 0 and bool(checks)
               and all(c["value"] <= c["limit"] for c in checks.values()))
    for k, v in sorted(e2e.items()):
        if k.startswith("_"):
            print(f"bench: {k[1:]} = {v}", file=sys.stderr)
    print(f"bench: compiles or compile-cache loads inside the window: "
          f"{in_window}", file=sys.stderr)
    print(f"bench: attempted {attempted}, failed {failed}", file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
