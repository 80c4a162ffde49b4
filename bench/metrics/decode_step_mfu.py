"""Model step: the whole decode step's share of the chip's peak.  The
fused steps the scheduler dispatched in the traced slice (its step
counter), serving the rows whose tokens the clients received there: the
least time the chip could take for that work (the larger of operations
over peak FLOP/s and bytes over peak bandwidth; bench/work.py), over the
slice, in %.  At these widths every step is bound by its bytes, so the
floor of the steps' summed work is the sum of their floors."""

from bench.readers import decode_contexts


def read(run):
    ctx = decode_contexts(run)
    if not ctx or not run.trace.steps:
        return None
    w = run.work.decode_steps(run.cell.config, run.cell.mix["head"],
                              run.trace.steps, ctx)
    return 100.0 * run.work_floor(w) / run.window_s
