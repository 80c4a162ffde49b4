"""Model step: mean device time of one fused decode-step program
execution (``jit_decode_step_<head>``) in the traced slice, in ms."""

from bench.spans import mean_ms


def read(run):
    return mean_ms(run.trace_mod.modules(run.trace.events,
                                         "jit_decode_step_"))
