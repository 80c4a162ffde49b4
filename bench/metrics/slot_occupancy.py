"""Decode scheduler: mean share of the KV pool's slots active per fused
step over the window before a profiler starts
(``RuntimeStats.decode_slot_occupancy``), in %."""


def read(run):
    occ = run.impl.counters.get("decode_slot_occupancy")
    return None if occ is None else 100.0 * occ
