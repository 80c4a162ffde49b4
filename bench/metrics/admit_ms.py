"""Decode scheduler: mean host time of admitting one session (the
program's ``decode.admit`` spans in the traced slice: prefill dispatch,
KV join, first-token rank and its host sync, first emit), in ms."""

from bench.spans import host_spans, mean_ms


def read(run):
    return mean_ms(host_spans(run.trace.events, "decode.admit"))
