"""Batched CMAX megakernel: the full engine pass as ONE pallas_call.

This is the §2 playbook taken to its limit: where `iwe_accum` +
`blur_stats` split the engine pass into two kernels joined by an HBM round
trip of the (4, H_s, W_s) channel stack, and the batched serving path was
`vmap` over per-window kernels (the grid never saw the batch axis), this
kernel fuses

    bilinear one-hot vote (MXU dot)  ->  row-slab accumulation in VMEM
    ->  streaming separable blur through a VMEM line buffer
    ->  Eq. 12 eight-sum statistics

into a single kernel whose grid is **(batch, slab)**: a B-window batch is
one kernel launch, the per-(b, slab) accumulator lives in VMEM across all
fused stages, and the only HBM write per window is its stats block.

  FPGA mechanism                      batched-grid realization here
  ------------------------------      -------------------------------------
  pixel-grouped sorting (Alg. 3)      taps binned by (window, row-slab) in
                                      the jnp prologue; grid step (b, i)
                                      streams only its slab's taps
  shared warp front-end (Alg. 2)      the prologue warps each event once
                                      per pass and both bins the taps and
                                      forms their deltas from that one
                                      warp, so a tap is voted into exactly
                                      the slab it was binned to
  conflict-free banked voting         one-hot x delta MXU contraction — no
                                      RMW hazard exists at all
  local accumulation + pending merge  the slab accumulates in VMEM and is
                                      consumed in place by the blur; the
                                      full channel stack NEVER reaches HBM
  36 line buffers (blur)              (K-1, 4, Wp) VMEM scratch carried
                                      across the slab axis of the grid
  on-the-fly statistics (Eq. 12)      (2, 4, Wp) VMEM running sums, reduced
                                      and flushed once per window
  outlier FIFO (fixed depth)          fixed per-(b, slab) tap capacity;
                                      spills are counted per window

The tile of the (batch, tile) grid is a full-width row slab (RB x Wp):
that is the unique tiling on which the vote's spatial partition and the
blur's sequential line-buffer streaming coincide, so all stages can share
one accumulator residency.

Grid iteration order matters: the slab axis is the fastest-varying grid
dimension, so for each window b the slabs run top-to-bottom and the line
buffer / stats scratch carry exactly that window's state (both are reset
at slab 0). TPU grids are sequential per core, which makes this carry
legal — the same property `blur_stats` already exploits.

Numerics: each (b, i) step reads only window b's records and runs the
same instructions for every b, so the compiled kernel gives a window the
same stats whatever batch it rides in. What the serving layer relies on
is the weaker contract the whole pipeline keeps: at a FIXED batch size a
slot's result depends only on that slot's inputs (tests/test_megakernel.py
pins both).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import resolve_interpret
from .blur_stats import blur_rows_into_stats, emit_stats
from .iwe_accum import CHUNK, onehot_vote


def _kernel(pix_ref, delta_ref, taps_ref, out_ref, lb_ref, acc_ref, *,
            rb: int, k: int, H: int, W: int, Wp: int, n_slabs: int):
    """One grid step: the fused vote/blur/stats pass for slab i of
    window b."""
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _reset():
        lb_ref[...] = jnp.zeros_like(lb_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # one-hot vote -> (4, RB*Wp) slab accumulator, then split into rows
    slab = onehot_vote(pix_ref, delta_ref, rb * Wp)
    rows = [slab[:, r * Wp:(r + 1) * Wp] for r in range(rb)]
    blur_rows_into_stats(rows, i, taps_ref, lb_ref, acc_ref, k=k, H=H, W=W)

    @pl.when(i == n_slabs - 1)
    def _emit():
        emit_stats(acc_ref, out_ref)


@functools.partial(
    jax.jit,
    static_argnames=("rb", "k", "H", "W", "Wp", "interpret"))
def megakernel_stats(pix, deltas, fir_taps, *, rb: int, k: int,
                     H: int, W: int, Wp: int,
                     interpret: Optional[bool] = None) -> jax.Array:
    """pallas_call wrapper: slab-binned tap records -> (B, 8, 128) stats
    blocks (`blur_stats.stats_from_block` decodes them).

    pix: (B, NS, 1, CAP) int32 slab-local pixel ids (row * Wp + col,
    -1 = padded slot); deltas: (B, NS, 4, CAP) channel deltas; fir_taps:
    (K,) blur FIR. ONE launch for the whole batch: grid = (B, NS) with the
    slab axis fastest, so per-window scratch (line buffer + running sums)
    is carried across each window's slabs and flushed to HBM exactly once
    per window. CAP must be a multiple of `iwe_accum.CHUNK`."""
    B, n_slabs, _, cap = pix.shape
    if cap % CHUNK:
        raise ValueError(f"capacity {cap} is not a multiple of {CHUNK}")
    if Wp % 128 or Wp < W + k // 2 or n_slabs * rb < H + k // 2:
        raise ValueError("slabs must cover H + k//2 rows and Wp >= W + k//2 "
                         "lane-aligned columns")
    kern = functools.partial(
        _kernel, rb=rb, k=k, H=H, W=W, Wp=Wp, n_slabs=n_slabs)
    return pl.pallas_call(
        kern,
        grid=(B, n_slabs),
        in_specs=[
            pl.BlockSpec((None, None, 1, cap), lambda b, i: (b, i, 0, 0)),
            pl.BlockSpec((None, None, 4, cap), lambda b, i: (b, i, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),          # blur taps
        ],
        out_specs=pl.BlockSpec((None, 8, 128), lambda b, i: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 8, 128), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((k - 1, 4, Wp), jnp.float32),     # line buffer
            pltpu.VMEM((2, 4, Wp), jnp.float32),         # running sums
        ],
        interpret=resolve_interpret(interpret),
    )(pix, deltas, fir_taps)
