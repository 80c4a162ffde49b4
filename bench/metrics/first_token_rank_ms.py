"""Engine step: mean device time of one score-step program execution
(``jit_score_step_<head>``) in the traced slice, in ms.  In a decode
cell these are the first-token ranks that follow each prefill."""

from bench.spans import mean_ms


def read(run):
    return mean_ms(run.trace_mod.modules(run.trace.events, "jit_score_step_"))
