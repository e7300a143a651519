"""Whole prefill step's share of the bf16 peak of all the cell's chips:
classical FLOPs of the real prompt tokens over the device time of
``jit_chunk_step`` on the first device times the chips (FalconGEMM dispatch
and LCMA execution, read over the whole step)."""
from bench import readers


def read(run):
    return readers.prefill_mfu(run)
