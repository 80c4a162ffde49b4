"""The on-chip benchmark: cells of ``BENCHMARK.json`` run by ``run.py``.

Everything that measures lives here and nowhere in the program: traffic
generation, weights from the seed, the plain references and the
comparison that decides ``correct``, the reduction from traces to
numbers, the work of each kernel and step, and the chip's peaks.  The
program is only driven through its serving entry points.
"""
