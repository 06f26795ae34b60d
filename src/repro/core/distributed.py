"""Data-parallel CMAX: estimate many event windows across devices.

Edge deployment is single-chip, but fleet-scale workloads (dataset-wide
motion ground-truthing, hyperparameter sweeps over tau/step schedules,
multi-camera rigs, and the batched estimation service in launch/serve.py)
batch thousands of independent windows — a pure data-parallel problem.
Windows shard over the (pod, data) axes; on each device the local slots
run one after another, each with its own adaptive while_loops (the
batched megakernel runs them in masked lockstep instead: a window that
converged early contributes masked no-ops, the SIMT analog of the
controller's clock gating). The energy model keeps per-window true
iteration counts either way.

Two entry points, both free of collectives in the step (verified by
tests/test_sharding_subprocess):

  * `estimate_batch_sharded(windows, omega0s, cfg, mesh)` — shard_map over
    the DP axes of a (B, N) padded window batch: each device runs the full
    coarse-to-fine adaptive pipeline on its local B/ndev shard. B must be
    divisible by the DP extent; the serving layer pads batches to class
    sizes that satisfy this (launch/serve.py), so it holds by
    construction there.
  * `estimate_streams_sharded(windows, omega_inits, cfg, mesh)` — the same
    for (S, K, N) stream batches with warm-start chaining inside each
    stream (scan over K, vmap over the local S shard).

Sharded results come back with the same leading axis layout they went in
with, so callers index them exactly like the single-device results of
`core.pipeline.estimate_batch` / `estimate_streams`.
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import PartitionSpec as P

from .pipeline import (WindowResult, estimate_streams,
                       estimate_windows_parallel)
from .types import CmaxConfig, EventWindow


def _dp_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def _dp_extent(mesh) -> int:
    n = 1
    for a in _dp_axes(mesh):
        n *= mesh.shape[a]
    return n


def _leading_axis_specs(fn, dp, *abstract_args):
    """out_specs pytree: every output leaf carries the batch on axis 0."""
    out = jax.eval_shape(fn, *abstract_args)
    return jax.tree.map(lambda a: P(dp, *([None] * (a.ndim - 1))), out)


# Jitted shard_map programs keyed on (kind, cfg, mesh). jax.jit caches by
# function identity, so rebuilding the shard_map wrapper per call would
# retrace/recompile every batch; one wrapper per (cfg, mesh) lets jit's own
# shape-keyed cache do its job. Output *ranks* (all out_specs depend on)
# are fixed per entry point, so specs built from the first call's shapes
# stay valid for every later shape.
_SHARDED_FNS = {}


def _sharded_fn(kind: str, local, in_specs, cfg, mesh, dp, windows, omegas):
    key = (kind, cfg, mesh)
    fn = _SHARDED_FNS.get(key)
    if fn is None:
        out_specs = _leading_axis_specs(local, dp, windows, omegas)
        fn = jax.jit(jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                                   out_specs=out_specs, check_vma=False))
        _SHARDED_FNS[key] = fn
    return fn


def estimate_batch_sharded(windows: EventWindow, omega0s: jax.Array,
                           cfg: CmaxConfig, mesh) -> WindowResult:
    """shard_map-backed `estimate_batch`: (B, N) windows + (B, 3) warm
    starts, B divisible by the DP extent. Each device runs its local shard
    through the full adaptive pipeline; there are no cross-device
    collectives, so scaling is embarrassingly linear."""
    dp = _dp_axes(mesh)
    ndev = _dp_extent(mesh)
    B = windows.x.shape[0]
    if B % ndev:
        raise ValueError(
            f"batch {B} not divisible by DP extent {ndev}; pad the batch "
            f"(launch/serve.py pads to class sizes automatically)")
    local = lambda w, o: estimate_windows_parallel(w, o, cfg)
    fn = _sharded_fn("batch", local, (P(dp, None), P(dp, None)),
                     cfg, mesh, dp, windows, omega0s)
    return fn(windows, omega0s)


def estimate_streams_sharded(windows: EventWindow, omega_inits: jax.Array,
                             cfg: CmaxConfig, mesh
                             ) -> Tuple[jax.Array, WindowResult]:
    """shard_map-backed `estimate_streams`: (S, K, N) stream batches with
    warm-start chaining per stream; S divisible by the DP extent."""
    dp = _dp_axes(mesh)
    ndev = _dp_extent(mesh)
    S = windows.x.shape[0]
    if S % ndev:
        raise ValueError(f"streams {S} not divisible by DP extent {ndev}")
    local = lambda w, o: estimate_streams(w, o, cfg)
    fn = _sharded_fn("streams", local, (P(dp, None, None), P(dp, None)),
                     cfg, mesh, dp, windows, omega_inits)
    return fn(windows, omega_inits)

