"""Runtime completion: mean host time of the completion thread's work on
one chunk once its results are ready (the program's ``runtime.record``
spans in the traced slice: copies to the host, ``Engine._record``,
resolving the futures), in ms."""

from bench.spans import host_spans, mean_ms


def read(run):
    return mean_ms(host_spans(run.trace.events, "runtime.record"))
