# Kernel layer: the compute hot-spots the paper optimizes in hardware,
# re-derived as Pallas TPU kernels (see DESIGN.md §2 for the mapping).
# The batched megakernel fuses the engine pass after the warp — vote,
# accumulate, blur, stats — into one (batch, slab)-grid pallas_call. A TPU
# compiles the kernels; the CPU interprets them (backend.py).
from .ops import (BatchedEngineOut, IweAccumOut, batched_engine_pass,
                  batched_engine_stats, blur_stats, fused_engine_pass,
                  iwe_accum)
from . import ref

__all__ = ["BatchedEngineOut", "IweAccumOut", "batched_engine_pass",
           "batched_engine_stats", "blur_stats", "fused_engine_pass",
           "iwe_accum", "ref"]
