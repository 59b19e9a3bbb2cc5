"""Pallas TPU kernel: segmented max-plus (Lindley) scan.

The fast fabric engine's hot spot: a segmented running maximum over packets
sorted by (queue, arrival).  TPU mapping:

  * the packet stream is laid out as ``(n / 128, 128)`` rows and tiled into
    VMEM blocks of ``block`` elements (``block / 128`` rows, a multiple of
    8 on TPU);
  * the TPU grid executes sequentially, so a VMEM scratch row carries the
    running maximum of the open segment across blocks;
  * within a block the segmented scan is a Hillis-Steele doubling scan over
    (value, flag) pairs -- first along the lanes of each row (``pltpu.roll``
    shifts), then over the rows' tails down the sublanes -- identical
    algebra to the associative_scan oracle in ``ref.py``.  ``max`` is exact
    and associative, so any combination order gives the same bits.

Flags are passed as int32 (bool VMEM blocks are awkward on TPU); any nonzero
means "segment start".
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .._common import NEG

_LANES = 128


def _shift(x, k, axis, fill):
    """``x`` moved ``k`` places towards higher indices along ``axis``, the
    vacated head filled with ``fill``."""
    pos = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    return jnp.where(pos >= k, pltpu.roll(x, k, axis), fill)


def _scan_axis(v, f, axis):
    """Inclusive segmented cummax of (v, f) along ``axis`` by doubling."""
    k = 1
    while k < v.shape[axis]:
        vp = _shift(v, k, axis, NEG)
        fp = _shift(f, k, axis, 0)
        v = jnp.where(f != 0, v, jnp.maximum(v, vp))
        f = f | fp
        k *= 2
    return v, f


def _scan_block(v, f):
    """Row-major segmented cummax of a (rows, 128) block."""
    v, f = _scan_axis(v, f, 1)
    # Carry into each row: the inclusive scan of the earlier rows' tails.
    tv = jnp.broadcast_to(v[:, _LANES - 1:], v.shape)
    tf = jnp.broadcast_to(f[:, _LANES - 1:], f.shape)
    tv, tf = _scan_axis(tv, tf, 0)
    cv, cf = _shift(tv, 1, 0, NEG), _shift(tf, 1, 0, 0)
    return jnp.where(f != 0, v, jnp.maximum(v, cv)), f | cf


def _kernel(v_ref, f_ref, o_ref, carry_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        carry_ref[...] = jnp.full(carry_ref.shape, NEG, jnp.float32)

    sv, sf = _scan_block(v_ref[...], f_ref[...])
    # positions with no flag anywhere before them in this block continue the
    # previous block's open segment:
    out = jnp.where(sf != 0, sv, jnp.maximum(sv, carry_ref[...]))
    o_ref[...] = out
    rows = out.shape[0]
    carry_ref[...] = jnp.broadcast_to(out[rows - 1:, _LANES - 1:],
                                      carry_ref.shape)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def segmented_cummax(v: jnp.ndarray, flags: jnp.ndarray, *,
                     interpret: bool, block: int = 1024) -> jnp.ndarray:
    """Segmented running max of ``v`` resetting where ``flags`` is set.

    Pads to a block multiple (padding opens a fresh segment so it never
    contaminates real data).  ``block`` is a multiple of 128; on TPU,
    of 1024.  ``interpret=True`` runs the kernel body in Python (the CPU
    validation path).
    """
    if block % _LANES:
        raise ValueError(f"block {block} is not a multiple of {_LANES}")
    n = v.shape[0]
    v = jnp.asarray(v, jnp.float32)
    f = jnp.asarray(flags).astype(jnp.int32)
    npad = (-n) % block
    if npad:
        v = jnp.concatenate([v, jnp.full((npad,), NEG)])
        f = jnp.concatenate([f, jnp.ones((npad,), jnp.int32)])
    rows = block // _LANES
    total = v.shape[0] // _LANES

    spec = pl.BlockSpec((rows, _LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        _kernel,
        grid=(total // rows,),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((total, _LANES), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, _LANES), jnp.float32)],
        interpret=interpret,
    )(v.reshape(total, _LANES), f.reshape(total, _LANES))
    return out.reshape(-1)[:n]
