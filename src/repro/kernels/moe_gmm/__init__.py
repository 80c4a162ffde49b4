"""Grouped matrix product over the routed experts a layer holds: the
expert half of a drop-free MoE layer as one op.

Layout: ``kernel.py`` (the Pallas TPU pass, whose custom call the
profiler names ``moe_gmm_pallas``), ``ref.py`` (the jnp oracle),
``ops.py`` (registry dispatch, tiling, row padding).
"""

from repro.kernels.moe_gmm.ops import moe_gmm
__all__ = ["moe_gmm"]
