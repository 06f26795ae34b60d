"""Pallas TPU kernel: tile-partitioned IWE/dIWE accumulation.

This is the TPU-native re-derivation of the paper's memory-centric
accumulation engine (DESIGN.md §2):

  FPGA mechanism                      TPU realization here
  ------------------------------     --------------------------------------
  pixel-grouped sorting (Alg. 3)      taps sorted by VMEM *tile* id; each
                                      grid step streams only its tile's taps
  conflict-free banked voting         the one-hot matmul has no RMW hazard
                                      at all — votes become systolic compute
                                      on the MXU instead of serialized SRAM
                                      read-modify-writes
  local accumulation + pending merge  the whole tile accumulates in VMEM and
                                      commits to HBM exactly once (the
                                      strongest form of pending merge)
  outlier FIFO (fixed depth)          fixed per-tile tap capacity; spills
                                      are counted and handled by the wrapper

Each grid step t processes the CAP tap slots that land in spatial tile t
and produces the (4, P_TILE) channel-major partial image of that tile:

    onehot[p, e] = (pix_local[e] == p)                  # (P_TILE, chunk)
    tile[c, p]  += sum_e delta[c, e] * onehot[p, e]     # MXU dot, per chunk

Everything is lane-major (the tap-slot and pixel axes run along the 128
lanes), so every block either spans its array's last two dims or is
(8, 128)-aligned. Invalid/padded slots carry pix_local = -1 and zero
deltas, so they vanish in the comparison. Accumulation is always f32
(`preferred_element_type`), whatever the delta dtype (f32/bf16 sweeps in
tests); f32 deltas contract at full f32 precision.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import resolve_interpret

#: tap slots voted per MXU contraction. The one-hot of one chunk is
#: (pixels, CHUNK) f32; at the megakernel's largest slab (8 x 256 pixels)
#: that is 2 MiB, well inside the default scoped VMEM limit.
CHUNK = 256


def onehot_vote(pix_ref, delta_ref, n_pix: int) -> jax.Array:
    """Vote the tap slots of one tile into a (4, n_pix) f32 partial image.

    pix_ref: (1, CAP) int32 tile-local pixel ids (-1 = padded slot);
    delta_ref: (4, CAP) per-slot channel deltas. CAP is a multiple of
    CHUNK; each chunk is one one-hot MXU contraction."""
    cap = pix_ref.shape[-1]
    dtype = delta_ref.dtype
    precision = (jax.lax.Precision.HIGHEST if dtype == jnp.float32
                 else None)
    iota = jax.lax.broadcasted_iota(jnp.int32, (n_pix, CHUNK), 0)

    def body(c, acc):
        off = pl.multiple_of(c * CHUNK, CHUNK)
        pix = pix_ref[:, pl.ds(off, CHUNK)]                  # (1, CHUNK)
        delta = delta_ref[:, pl.ds(off, CHUNK)]              # (4, CHUNK)
        onehot = (iota == pix).astype(dtype)                 # (n_pix, CHUNK)
        return acc + jax.lax.dot_general(
            delta, onehot, dimension_numbers=(((1,), (1,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32)

    return jax.lax.fori_loop(0, cap // CHUNK, body,
                             jnp.zeros((4, n_pix), jnp.float32))


def _kernel(pix_ref, delta_ref, out_ref, *, p_tile: int):
    out_ref[...] = onehot_vote(pix_ref, delta_ref, p_tile)


@functools.partial(jax.jit, static_argnames=("p_tile", "interpret"))
def tile_accumulate(pix_local: jax.Array, deltas: jax.Array, *, p_tile: int,
                    interpret: Optional[bool] = None) -> jax.Array:
    """pallas_call wrapper: (T, 1, CAP) local pixel ids + (T, 4, CAP)
    deltas -> (T, 4, P_TILE) tile partials, channel-major. Grid is one
    step per spatial tile; CAP must be a multiple of CHUNK."""
    n_tiles, _, cap = pix_local.shape
    if cap % CHUNK:
        raise ValueError(f"capacity {cap} is not a multiple of {CHUNK}")
    kern = functools.partial(_kernel, p_tile=p_tile)
    return pl.pallas_call(
        kern,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((None, 1, cap), lambda t: (t, 0, 0)),
            pl.BlockSpec((None, 4, cap), lambda t: (t, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, 4, p_tile), lambda t: (t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_tiles, 4, p_tile), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(pix_local, deltas)
