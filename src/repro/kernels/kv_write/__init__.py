"""Write a decode step's new KV rows into the layer-stacked caches in
place: row ``r`` at position ``pos[r]`` of layer ``layer``.

Layout: ``kernel.py`` (the Pallas TPU pass for caches whose sequence axis
lies on the lanes), ``ref.py`` (per-row updates, the jnp oracle),
``ops.py`` (registry dispatch).
"""

from repro.kernels.kv_write.ops import kv_write
__all__ = ["kv_write"]
