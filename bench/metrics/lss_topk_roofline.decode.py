"""Kernel: the fused ``lss_topk`` kernel's device time in the traced slice
against the required work of the head queries it served (every streamed
token), in % of its roofline."""

from bench.readers import decode_rows, kernel_roofline


def read(run):
    return kernel_roofline(run, "lss_topk_pallas", decode_rows(run), "lss")
