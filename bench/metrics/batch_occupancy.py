"""Runtime admission and batching: mean fill of the bucketed chunks the
dispatcher ran in the window before a profiler starts
(``RuntimeStats.avg_batch_occupancy``), in %."""


def read(run):
    occ = run.impl.counters.get("avg_batch_occupancy")
    return None if occ is None else 100.0 * occ
