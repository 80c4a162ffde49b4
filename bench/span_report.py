#!/usr/bin/env python3
"""Run one cell traced, as ``bench/run.py --trace 1`` does, and write what
its trace says of the program's spans to a JSON file.

    python3 bench/span_report.py --workload <cell> --seed <n> --seconds <s> --out <file>

From the root of a checkout.  The run's own result line is printed as
``bench/run.py`` prints it.  The file holds:

- ``idle``: the device's idle seconds between operations in the traced
  slice, those inside any program span, and those inside each span name
  (``spans.idle_in_spans``);
- ``gaps``: the ten longest idle gaps, each named by the innermost
  program span covering half of it (``spans.gaps_by_span``);
- ``spans`` / ``modules``: count and mean ms of each program span and of
  each program (``XLA Modules``) in the slice;
- ``queue_wait_ms_host`` (score cells): mean submit → dispatch from the
  window's start to where the profiler starts, the reading of
  ``queue_wait_ms`` without the traced slice.

On a program without the spans or counters those entries are empty.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _mean_ms(events) -> list:
    return [len(events), 1e3 * sum(e.dur for e in events) / len(events)]


def report(events, c0=None, c_host=None) -> dict:
    from bench import spans, trace
    by_span: dict = {}
    for e in spans.program_spans(events):
        by_span.setdefault(e.name, []).append(e)
    by_module: dict = {}
    for e in events:
        if e.line == trace.MODULES_LINE:
            by_module.setdefault(e.name.split("(")[0], []).append(e)
    out = {"idle": spans.idle_in_spans(events),
           "gaps": spans.gaps_by_span(events),
           "spans": {k: _mean_ms(v) for k, v in sorted(by_span.items())},
           "modules": {k: _mean_ms(v) for k, v in sorted(by_module.items())}}
    if c0 is not None and hasattr(c0, "queue_wait_s_total"):
        n = c_host.n_dispatched - c0.n_dispatched
        if n > 0:
            out["queue_wait_ms_host"] = 1e3 * (
                c_host.queue_wait_s_total - c0.queue_wait_s_total) / n
    return out


def main(argv=None, require_chip: bool = True,
         root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import run, trace
    from bench.score import ScoreCell

    kept: dict = {}
    load = trace.load_events
    mark = ScoreCell.mark_host_end

    def load_and_keep(path):
        kept["events"] = load(path)
        return kept["events"]

    def mark_and_keep(self):
        mark(self)
        kept["c0"], kept["c_host"] = self._c0, self.rt.stats()

    trace.load_events = load_and_keep
    ScoreCell.mark_host_end = mark_and_keep
    rc = run.main(["--workload", args.workload, "--seed", args.seed,
                   "--seconds", args.seconds, "--trace", "1"],
                  require_chip=require_chip, root=root)
    if "events" in kept:
        out = Path(args.out)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(report(kept["events"], kept.get("c0"),
                                         kept.get("c_host"))))
    return rc


if __name__ == "__main__":
    sys.exit(main())
