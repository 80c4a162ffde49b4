"""Load generator: 95th percentile, over every request due in the window
before a profiler starts, of how late the pacer handed it to the runtime
(host clock)."""

from bench.stats import percentile


def read(run):
    req = getattr(run.impl, "req", None)
    if req is None:
        return None
    w = run.impl.window_mask() & (req.due < run.impl.t_host)
    if not w.any():
        return None
    return percentile((req.sent[w] - req.due[w]).tolist(), 95) * 1e3
