"""GPipe-style pipeline parallelism over a mesh axis (optional feature).

The default dry-run path uses pod-as-data (keeps the roofline comparable
across archs); this module provides the alternative: split the layer stack
into S stages along the `pipe` axis and stream M microbatches through with
`collective_permute` between stages (the classic GPipe schedule with
M + S - 1 ticks; bubble fraction (S-1)/(M+S-1)).

Differentiable end-to-end: the transpose of ppermute is the reverse
permute, so jax.grad produces the standard backward pipeline schedule.
Validated against the sequential reference in
tests/test_pipeline_parallel.py.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

PyTree = object


def _pipe_shard(params_loc: PyTree, mbs: jax.Array, *,
                stage_fn: Callable, n_stages: int, axis: str) -> jax.Array:
    """Per-stage body. params_loc: this stage's layer stack (leading layer
    axis already sliced to L/S). mbs: (M, mb, ...) microbatches
    (replicated). Returns (M, mb, ...) outputs (valid on every shard after
    the final psum)."""
    sid = jax.lax.axis_index(axis)
    M = mbs.shape[0]
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def tick(t, carry):
        recv, out = carry
        # stage 0 injects microbatch t (clipped; masked out later via the
        # output index check), others consume what stage s-1 sent
        x_in = jnp.where(sid == 0, mbs[jnp.clip(t, 0, M - 1)], recv)
        h = stage_fn(params_loc, x_in)
        send = jax.lax.ppermute(h, axis, perm)
        idx = t - (n_stages - 1)
        write = (sid == n_stages - 1) & (idx >= 0) & (idx < M)
        upd = jax.lax.dynamic_update_index_in_dim(
            out, h, jnp.clip(idx, 0, M - 1), 0)
        out = jnp.where(write, upd, out)
        return send, out

    # initial carries must be marked as device-varying for the fori_loop
    # type check (they become varying through ppermute/axis_index)
    recv0 = jax.lax.pcast(jnp.zeros_like(mbs[0]), (axis,), to="varying")
    out0 = jax.lax.pcast(jnp.zeros_like(mbs), (axis,), to="varying")
    _, out = jax.lax.fori_loop(0, M + n_stages - 1, tick, (recv0, out0))
    # only the last stage holds real outputs; replicate via masked psum
    out = jnp.where(sid == n_stages - 1, out, 0.0)
    return jax.lax.psum(out, axis)


def pipeline_apply(stage_fn: Callable, stacked_params: PyTree,
                   x: jax.Array, mesh, *, n_microbatches: int,
                   axis: str = "pipe") -> jax.Array:
    """Run x (B, ...) through the pipelined layer stack.

    stage_fn(stage_params, h) applies one stage's layers (stage_params
    leaves have a leading per-stage layer axis). stacked_params leaves have
    a leading TOTAL layer axis divisible by the pipe axis size; they are
    sharded over `axis` so each shard holds only its stage's layers.
    """
    S = mesh.shape[axis]
    B = x.shape[0]
    assert B % n_microbatches == 0
    mbs = x.reshape(n_microbatches, B // n_microbatches, *x.shape[1:])

    body = functools.partial(_pipe_shard, stage_fn=stage_fn, n_stages=S,
                             axis=axis)
    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis), stacked_params),
                  P(*([None] * (mbs.ndim)))),
        out_specs=P(*([None] * mbs.ndim)),
    )(stacked_params, mbs)
    return out.reshape(B, *x.shape[1:])


def sequential_reference(stage_fn: Callable, stacked_params: PyTree,
                         x: jax.Array, n_stages: int) -> jax.Array:
    """The math the pipeline must reproduce: apply all stages in order."""
    h = x
    for s in range(n_stages):
        p_s = jax.tree.map(
            lambda a: a[s * (a.shape[0] // n_stages):
                        (s + 1) * (a.shape[0] // n_stages)], stacked_params)
        h = stage_fn(p_s, h)
    return h
