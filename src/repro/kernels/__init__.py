"""Pallas TPU kernels for the engine's compute hot-spots.

triplet     — general fused mrTriplets sweep: gather(src,dst) + map UDF +
              segment reduce (sum/min/max) in one kernel (DESIGN.md §2.3)
segment_sum — CSR message aggregation (the unfused mrTriplets reduce)
flash_attention — LM-substrate attention

Each kernel: <name>.py (pl.pallas_call + BlockSpec), wrapped by ops.py,
oracled by ref.py.
"""
from . import ops, ref

__all__ = ["ops", "ref"]
