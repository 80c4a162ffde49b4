"""Runtime admission and batching: mean wait from ``submit`` to dispatch
of the scoring requests the dispatcher ran since the window opened (the
runtime's ``queue_wait_s_total`` over ``n_dispatched``, read when the
run has ended, less their values at the window's start), in ms."""


def read(run):
    c0 = getattr(run.impl, "_c0", None)
    if c0 is None or not hasattr(c0, "queue_wait_s_total"):
        return None
    c1 = run.impl.rt.stats()
    n = c1.n_dispatched - c0.n_dispatched
    if n <= 0:
        return None
    return 1e3 * (c1.queue_wait_s_total - c0.queue_wait_s_total) / n
