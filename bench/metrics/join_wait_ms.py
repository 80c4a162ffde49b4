"""Decode scheduler: mean wait of a session from ``submit_decode`` to the
start of its admission to a slot (the scheduler's ``join_wait_s_total``
over ``n_joined``) in the window before a profiler starts, in ms."""


def read(run):
    c = run.impl.counters
    n = c.get("n_joined")
    if not n:
        return None
    return 1e3 * c["join_wait_s_total"] / n
