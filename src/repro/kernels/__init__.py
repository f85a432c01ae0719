"""Pallas TPU kernels for the compute hot spots, with jnp oracles.

  * ``flash_attention`` — online-softmax attention; removes the O(S*T)
    score traffic that makes the reference path memory-bound (§Roofline).
  * ``ssd_scan``        — Mamba-2 chunked SSD with VMEM-resident
    inter-chunk state.

Kernels target TPU (``pl.pallas_call`` + BlockSpec VMEM tiling) and are
validated on CPU in interpret mode against ``<kernel>/ref.py``;
``tests/test_v5e_compile.py`` compiles them for a described v5e.
``sim_scan/ref.py`` is the simulator's duration sampler, plain jnp only.
"""

from . import ops
from .ops import flash_attention, ssd_scan

__all__ = ["ops", "flash_attention", "ssd_scan"]
