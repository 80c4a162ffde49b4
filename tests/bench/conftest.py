"""The tiny checkouts of ``tinycells.make_root`` list, for each metric, the
tiny cells that stand for the benchmark's cells.  The qwen2-moe cell has
its own tiny cell, built by ``test_bench_qwen2_moe.py``, and none among
``make_root``'s."""

import tinycells

tinycells.TINY_OF.setdefault("qwen2-moe-a2.7b.decode.lss", [])
