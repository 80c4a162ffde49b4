"""Model step: mean device time of one prefill program execution
(``jit__prefill_jit``) in the traced slice, in ms."""


def read(run):
    evs = run.trace_mod.modules(run.trace.events, "jit__prefill_jit")
    if not evs:
        return None
    return 1e3 * sum(e.dur for e in evs) / len(evs)
