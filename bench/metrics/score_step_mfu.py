"""Engine step: required work of the requests the score steps served in
the traced slice, at the chip's peaks, over the device's busy time there
(only score steps run on the device in a score cell), in %.  The steps
are the chunks the dispatcher ran in the slice (its batch counter)."""

from bench.readers import score_rows


def read(run):
    rows = score_rows(run)
    n = run.trace.steps
    if rows == 0 or not n or run.busy_s <= 0:
        return None
    # one program per chunk; their rows share the chunks evenly
    per_call = run.work.head_call(run.cell.config, run.cell.mix["head"],
                                  rows / n)
    return 100.0 * n * run.work_floor(per_call) / run.busy_s
