"""Kernel: the fused ``lss_topk`` kernel's device time in the traced slice
against the required work of the requests it served, in % of its
roofline."""

from bench.readers import kernel_roofline, score_rows


def read(run):
    return kernel_roofline(run, "lss_topk_pallas", score_rows(run), "lss")
