"""DR-scheduled collective engine — the paper's discipline at the collective
layer.

The paper proves destination-based rotation (every communication round is a
*permutation*) achieves Theta(1) queueing where hash-based spraying gets
Omega(sqrt(m)) and round-robin Theta(m).  On a TPU/DCN deployment the
schedule of a collective plays the role the switch scheduler plays in the
fabric: XLA's one-shot ``all_to_all`` / ``all_gather`` leaves balancing to
the fabric, while a **rotation schedule** (n-1 ``ppermute`` rounds, each a
perfect permutation) is per-destination balanced *by construction*.

Implementations (all inside ``shard_map`` over a chosen mesh axis):

  all_gather:      'xla' | 'ring' (n-1 neighbor rounds)
  reduce_scatter:  'xla' | 'ring'
  all_reduce:      'xla' | 'rs_ag' (ring RS + ring AG -- the bandwidth-
                    optimal schedule; both phases are rotations)
  all_to_all:      'xla' | 'rotation' ((n-1) destination rotations -- the
                    paper's "(n-1) permutation matrices")

Every custom schedule is validated against its XLA counterpart in
``tests/test_collectives.py``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


# ---------------------------------------------------------------------------
# shard_map inner collectives (take local shard, return local shard)
# ---------------------------------------------------------------------------

def ring_all_gather(x_loc, axis: str, n: int):
    """(d0, ...) -> (n*d0, ...): n-1 rounds; round r forwards the block
    received in round r-1 to the next neighbor (each round is the rotation
    permutation i -> i+1)."""
    if n == 1:
        return x_loc
    me = jax.lax.axis_index(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]
    out = jnp.zeros((n,) + x_loc.shape, x_loc.dtype)
    out = jax.lax.dynamic_update_slice(
        out, x_loc[None], (me,) + (0,) * x_loc.ndim)
    blk = x_loc
    for r in range(1, n):
        blk = jax.lax.ppermute(blk, axis, perm)
        src = (me - r) % n
        out = jax.lax.dynamic_update_slice(
            out, blk[None], (src,) + (0,) * x_loc.ndim)
    return out.reshape((n * x_loc.shape[0],) + x_loc.shape[1:])


def ring_reduce_scatter(x_loc, axis: str, n: int):
    """(n*d0, ...) -> (d0, ...) summed across the axis; n-1 rotation rounds.

    The partial for destination block k starts at shard k+1 (value
    b_{k+1}[k]) and flows k+1 -> k+2 -> ... -> k, each visited shard j
    adding its own contribution b_j[k]; shard j therefore holds partial
    P_{j-r-1} after round r and finishes with P_j = sum_i b_i[j]."""
    if n == 1:
        return x_loc
    me = jax.lax.axis_index(axis)
    d0 = x_loc.shape[0] // n
    blocks = x_loc.reshape((n, d0) + x_loc.shape[1:])
    perm = [(i, (i + 1) % n) for i in range(n)]
    acc = jnp.take(blocks, (me - 1) % n, axis=0)       # P_{me-1} seed
    for r in range(1, n):
        acc = jax.lax.ppermute(acc, axis, perm)
        acc = acc + jnp.take(blocks, (me - r - 1) % n, axis=0)
    return acc


def rotation_all_to_all(x_loc, axis: str, n: int, split: int = 0,
                        concat: int = 0):
    """Tiled all-to-all as n-1 destination rotations (paper §2: an AlltoAll
    is (n-1) permutation matrices applied iteratively)."""
    if n == 1:
        return x_loc
    me = jax.lax.axis_index(axis)
    chunks = jnp.stack(jnp.split(x_loc, n, axis=split), axis=0)
    out_shape = list(chunks.shape[1:])
    out_shape[concat] *= n
    out = jnp.zeros(out_shape, x_loc.dtype)
    csz = chunks.shape[1:][concat]

    def put(arr, block, pos):
        start = [0] * arr.ndim
        start[concat] = pos * csz
        return jax.lax.dynamic_update_slice(arr, block, tuple(start))

    out = put(out, jnp.take(chunks, me, axis=0), me)
    for r in range(1, n):
        send = jnp.take(chunks, (me + r) % n, axis=0)
        recv = jax.lax.ppermute(send, axis,
                                [(i, (i + r) % n) for i in range(n)])
        out = put(out, recv, (me - r) % n)
    return out


def ring_all_reduce(x_loc, axis: str, n: int):
    """Bandwidth-optimal all-reduce: ring reduce-scatter + ring all-gather.
    Requires leading dim divisible by n."""
    if n == 1:
        return x_loc
    scat = ring_reduce_scatter(x_loc, axis, n)
    return ring_all_gather(scat, axis, n)


# ---------------------------------------------------------------------------
# Public (global-array) entry points
# ---------------------------------------------------------------------------

def _axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape[axis]


def all_gather(x, mesh: Mesh, axis: str, impl: str = "rotation"):
    """Gather shards of x (sharded on dim 0 over ``axis``) -> replicated."""
    n = _axis_size(mesh, axis)

    def inner(xl):
        if impl == "xla":
            return jax.lax.all_gather(xl, axis, axis=0, tiled=True)
        return ring_all_gather(xl, axis, n)

    return jax.shard_map(inner, mesh=mesh, in_specs=P(axis),
                     out_specs=P(), check_vma=False)(x)


def all_reduce(x, mesh: Mesh, axis: str, impl: str = "rotation"):
    """Sum x (replicated shards with distinct partials... i.e. psum) over
    ``axis``.  x must have leading dim divisible by the axis size for the
    ring schedule."""
    n = _axis_size(mesh, axis)

    def inner(xl):
        if impl == "xla":
            return jax.lax.psum(xl, axis)
        return ring_all_reduce(xl, axis, n)

    return jax.shard_map(inner, mesh=mesh, in_specs=P(), out_specs=P(),
                     check_vma=False)(x)


def reduce_scatter(x, mesh: Mesh, axis: str, impl: str = "rotation"):
    n = _axis_size(mesh, axis)

    def inner(xl):
        if impl == "xla":
            return jax.lax.psum_scatter(xl, axis, scatter_dimension=0,
                                        tiled=True)
        return ring_reduce_scatter(xl, axis, n)

    return jax.shard_map(inner, mesh=mesh, in_specs=P(), out_specs=P(axis),
                     check_vma=False)(x)


def all_to_all(x, mesh: Mesh, axis: str, impl: str = "rotation"):
    """x sharded on dim 0; block-transpose across the axis (tiled a2a)."""
    n = _axis_size(mesh, axis)

    def inner(xl):
        if impl == "xla":
            return jax.lax.all_to_all(xl, axis, split_axis=0, concat_axis=0,
                                      tiled=True)
        return rotation_all_to_all(xl, axis, n, split=0, concat=0)

    return jax.shard_map(inner, mesh=mesh, in_specs=P(axis), out_specs=P(axis),
                     check_vma=False)(x)
