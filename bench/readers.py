"""What the per-layer readers (``bench/metrics/``) share: the work served
inside the traced slice of a window, from the benchmark's own records
and the program's step counters read at the slice's ends.

A session's first token comes from its prefill; token ``k >= 1`` of a
session with a prompt of ``p`` tokens was produced by a fused decode
step that attended to ``p + k`` positions.  Times are the benchmark's
own: when the client received each token or answer.
"""

from __future__ import annotations


def decode_contexts(run) -> list[int]:
    """Contexts of the rows of the fused decode steps whose tokens were
    received inside the traced slice."""
    t_a, t_b = run.trace.t_a, run.trace.t_b
    out = []
    for r in run.impl.records:
        p = len(r.prompt)
        for k in range(1, len(r.token_times)):
            if t_a <= r.token_times[k] < t_b:
                out.append(p + k)
    return out


def decode_rows(run) -> int:
    """Head queries resolved inside the traced slice: every token, first
    tokens (ranked after prefill) included."""
    t_a, t_b = run.trace.t_a, run.trace.t_b
    return sum(int(((r.token_times >= t_a) & (r.token_times < t_b)).sum())
               for r in run.impl.records)


def score_rows(run) -> int:
    """Requests answered inside the traced slice."""
    req = run.impl.req
    done = req.done[~req.failed]
    return int(((done >= run.trace.t_a) & (done < run.trace.t_b)).sum())


def kernel_roofline(run, kernel: str, rows: int, head: str):
    """Required time of ``rows`` queries through the head's kernel over
    the kernel's device time in the trace, in %; None when the kernel did
    not run."""
    evs = run.trace_mod.kernel_events(run.trace.events, kernel)
    if not evs or rows == 0:
        return None
    t = sum(e.dur for e in evs)
    per_call = run.work.head_call(run.cell.config, head, rows / len(evs))
    floor = len(evs) * run.work_floor(per_call)
    return 100.0 * floor / t
