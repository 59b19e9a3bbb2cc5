"""Pallas kernels for the slotted engine's per-slot body.

Four fused ops (see ``ref.py`` for the oracle semantics):

  * :func:`jsq_pick` -- queue-occupancy gather + in-kernel Threefry
    tie-break noise (:mod:`repro.core.entropy` is written against the
    numpy/jnp-shared operator set, so the PRF evaluates inside the kernel
    body) + quantization + pad/dead penalties + masked argmin.  Tiled over
    choosers (``block``); the occupancy vector rides whole in VMEM.
  * :func:`enqueue` / :func:`agg_jsq_enqueue` -- the arrival enqueue
    update (same-queue ranking, capacity drops, ring-buffer write,
    occupancy add, ECN marks), optionally fused with the agg-layer JSQ
    pick so the pick and the occupancy it feeds stay in one VMEM-resident
    pass.  Single-program kernels: the ranking couples all lanes.
  * :func:`sack_update_scan` / :func:`sack_advance` -- receiver-bitmap
    update + per-flow first-missing window argmin, and the unrolled
    cumulative-ack advance rounds.

Under ``vmap`` (the engine's seed/mega batch axes) the fused campaign axis
becomes the leading kernel grid dimension via the ``pallas_call`` batching
rule -- one launch covers the megabatch.

Mosaic (the TPU kernel compiler) lowers no general gather or scatter, so
every indexed access is written in forms it does lower:

  * every operand is 2-D: a per-lane vector enters as a ``(m, 1)`` column
    or a ``(1, m)`` row (reshaped outside the kernel), and a value needed
    in both orientations is computed in both -- the same element-wise ops,
    so both copies are bitwise equal -- instead of transposed.  Mosaic
    (jax 0.9.0) transposes int32 blocks at these shapes but not boolean
    masks (``tpu.transpose`` of an i1 value fails to legalize), and the
    pick, ranking and drop logic mix both.  Transposing the int32 values
    and re-deriving the masks would drop the duplicated operands and the
    second pick; that is a speed question left open here;
  * a gather from a short vector is a one-hot compare-and-reduce over a
    2-D ``broadcasted_iota`` (1-D iota does not lower);
  * a scatter is a one-hot matmul: ``(queues x lanes) @ (lanes x slots)``
    with 0/1 bf16 operands and f32 accumulation.  Every output cell has at
    most one contributing lane, so the products are exact; int32 payloads
    travel as four 8-bit digits (exact in bf16) and are reassembled;
  * the packet bitmap is ``(P/128, 128)``; a flow's window of at most 128
    packets spans two of its rows, which a one-hot row matmul fetches.

Argmin is min-of-iota-where-min (bitwise-equal to ``jnp.argmin``
first-occurrence semantics), the same-slot arrival ranking an O(M^2)
masked count (``rank_by``'s stable sort has no Mosaic lowering).  Booleans
cross the kernel boundary as int32; the PRF key words and the slot ride
in SMEM.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core import entropy as ent

_LANES = 128
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _iota2(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _first_min_index(x, axis):
    """Index of the first minimum along ``axis`` (kept as a size-1 dim):
    bitwise-equal to ``jnp.argmin`` (min-reduction formulation lowers on
    TPU)."""
    m = jnp.min(x, axis=axis, keepdims=True)
    return jnp.min(jnp.where(x == m, _iota2(x.shape, axis), x.shape[axis]),
                   axis=axis, keepdims=True)


def _take_cols(row, idx):
    """``row[0, idx]`` for an ``(m, 1)`` index column -> ``(m, 1)``."""
    hit = _iota2((idx.shape[0], row.shape[1]), 1) == idx
    return jnp.sum(jnp.where(hit, row, 0), axis=1, keepdims=True)


def _take_rows(col, idx):
    """``col[idx, 0]`` for a ``(1, m)`` index row -> ``(1, m)``."""
    hit = _iota2((col.shape[0], idx.shape[1]), 0) == idx
    return jnp.sum(jnp.where(hit, col, 0), axis=0, keepdims=True)


def _onehot_dot(sel, val):
    """``sel @ val`` for 0/1 ``sel`` and small non-negative integer ``val``
    (< 256, exact in bf16), accumulated exactly in f32."""
    return jnp.dot(sel.astype(jnp.bfloat16), val.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)


def _col(x):
    return x.reshape(-1, 1)


def _row(x):
    return x.reshape(1, -1)


def _scalars(seed_lo, seed_hi, t):
    """The PRF key words and the slot as (1, 1) SMEM operands (2-D, so the
    vmapped block stays legal on TPU)."""
    return (jnp.asarray(seed_lo, jnp.uint32).reshape(1, 1),
            jnp.asarray(seed_hi, jnp.uint32).reshape(1, 1),
            jnp.asarray(t, jnp.int32).reshape(1, 1))


# ---------------------------------------------------------------------------
# JSQ pick
# ---------------------------------------------------------------------------

def _pick_body(qcnt, qbase, ids, dead, pen, s_lo, s_hi, t, *,
               site, quanta, cap, port_axis):
    """Score grid + masked argmin (mirrors ``ref.jsq_score``/``ref.jsq_pick``
    op for op).  ``port_axis`` 1: choosers down the sublanes, ``qcnt`` a
    ``(1, NQ)`` row, ``qbase``/``ids`` ``(m, 1)``, ``dead`` ``(m, h)``,
    ``pen`` ``(1, h)``; ``port_axis`` 0 is the transpose of all of it."""
    shape = dead.shape
    h = shape[port_axis]
    port = _iota2(shape, port_axis)
    take = _take_cols if port_axis == 1 else _take_rows
    lens = jnp.zeros(shape, jnp.int32)
    for p in range(h):
        lens = jnp.where(port == p, take(qcnt, qbase + p), lens)
    nz = ent.draw_uniform(s_lo, s_hi, site, ids, t, lane=port)
    if quanta is None:
        score = lens.astype(jnp.float32) + nz * 1e-3
    else:
        # Host-side f32 thresholds: identical rounding to the engine's
        # ``jnp.asarray(quanta, f32) * CAP``.
        thr = np.asarray(quanta, np.float32) * np.float32(cap)
        lf = lens.astype(jnp.float32)
        bins = jnp.zeros(shape, jnp.int32)
        for v in thr:
            bins = bins + (lf > jnp.float32(v)).astype(jnp.int32)
        score = bins.astype(jnp.float32) + nz * 0.5
    score = score + pen
    score = score + jnp.where(dead, jnp.float32(1e9), jnp.float32(0.0))
    return _first_min_index(score, port_axis)


def _jsq_pick_kernel(slo_ref, shi_ref, t_ref, qcnt_ref, qbase_ref, ids_ref,
                     dead_ref, pen_ref, o_ref, *, site, quanta, cap):
    o_ref[...] = _pick_body(
        qcnt_ref[...], qbase_ref[...], ids_ref[...], dead_ref[...] != 0,
        pen_ref[...], slo_ref[0, 0], shi_ref[0, 0], t_ref[0, 0],
        site=site, quanta=quanta, cap=cap, port_axis=1)


@functools.partial(jax.jit, static_argnames=("site", "quanta", "cap",
                                             "block", "interpret"))
def jsq_pick(qcnt, qbase, ids, dead, pad_pen, seed_lo, seed_hi, t, *,
             site, quanta, cap, interpret: bool, block=None):
    """Fused JSQ port pick; see ``ref.jsq_pick``.  ``block`` tiles the
    chooser axis (default: one program for the whole row; on TPU a
    multiple of 8); non-divisible tails are padded with inert choosers and
    sliced off."""
    M = qbase.shape[0]
    NQ = qcnt.shape[0]
    h = pad_pen.shape[0]
    block = M if block is None else min(int(block), M)
    npad = (-M) % block
    if npad:
        qbase = jnp.concatenate([qbase, jnp.zeros((npad,), qbase.dtype)])
        ids = jnp.concatenate([ids, jnp.zeros((npad,), ids.dtype)])
        dead = jnp.concatenate([dead, jnp.zeros((npad, h), bool)])
    out = pl.pallas_call(
        functools.partial(_jsq_pick_kernel, site=site, quanta=quanta,
                          cap=cap),
        grid=((M + npad) // block,),
        in_specs=[
            _SMEM, _SMEM, _SMEM,
            pl.BlockSpec((1, NQ), lambda i: (0, 0)),
            pl.BlockSpec((block, 1), lambda i: (i, 0)),
            pl.BlockSpec((block, 1), lambda i: (i, 0)),
            pl.BlockSpec((block, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M + npad, 1), jnp.int32),
        interpret=interpret,
    )(*_scalars(seed_lo, seed_hi, t), _row(qcnt), _col(qbase), _col(ids),
      dead.astype(jnp.int32), _row(pad_pen))
    return out[:M, 0]


# ---------------------------------------------------------------------------
# enqueue / agg_jsq_enqueue: single-program (ranking couples all lanes)
# ---------------------------------------------------------------------------

def _enqueue_body(qbuf, qhead_r, qhead_c, qcnt_r, qcnt_c, alive_r, alive_c,
                  apk_c, aq_c, aq_r, avalid_c, avalid_r, *, cap, ecn_thresh):
    """Mirrors ``ref.enqueue``.  Lane vectors come as ``_c`` columns
    ``(m, 1)`` and ``_r`` rows ``(1, m)``, queue vectors likewise over NQ.
    The rank is an O(M^2) masked count: ``rkq[i] = #{j < i : try[j] and
    aq[j] == aq[i]}`` -- the stable-sort rank of ``rank_by`` without the
    sort -- and the ring-buffer write a one-hot matmul."""
    nq = qcnt_c.shape[0]
    M = aq_c.shape[0]
    aqc_c = jnp.clip(aq_c, 0, nq - 1)
    aqc_r = jnp.clip(aq_r, 0, nq - 1)
    try_c = avalid_c & (_take_cols(alive_r, aqc_c) != 0)
    try_r = avalid_r & (_take_rows(alive_c, aqc_r) != 0)
    same = aq_c == aq_r                                   # [i, j]
    # Column rank: i down the sublanes, j across the lanes; row rank: the
    # same count with the roles swapped.
    rk_c = jnp.sum((same & try_r & (_iota2((M, M), 1) < _iota2((M, M), 0))
                    ).astype(jnp.int32), axis=1, keepdims=True)
    rk_r = jnp.sum((same & try_c & (_iota2((M, M), 0) < _iota2((M, M), 1))
                    ).astype(jnp.int32), axis=0, keepdims=True)
    rk_c = jnp.where(try_c, rk_c, 0)
    rk_r = jnp.where(try_r, rk_r, 0)
    cnt_c = _take_cols(qcnt_r, aqc_c)
    do_c = try_c & (cnt_c + rk_c < cap)
    do_r = try_r & (_take_rows(qcnt_c, aqc_r) + rk_r < cap)
    pos_c = (_take_cols(qhead_r, aqc_c) + cnt_c + rk_c) % cap
    occ_after = cnt_c + rk_c + 1
    marked = do_c & (occ_after > ecn_thresh)
    # Ring-buffer write: hit[q, c] = #lanes enqueued at (q, c), at most one.
    sel = (do_r & (aq_r == _iota2((nq, M), 0))).astype(jnp.int32)
    width = -(-cap // _LANES) * _LANES
    at = (pos_c == _iota2((M, width), 1)).astype(jnp.int32)
    hit = _onehot_dot(sel, at)[:, :cap]
    val = jnp.zeros(qbuf.shape, jnp.int32)
    for d in range(4):
        digit = (apk_c >> (8 * d)) & 0xFF
        val = val | (_onehot_dot(sel, at * digit)[:, :cap]
                     .astype(jnp.int32) << (8 * d))
    qbuf2 = jnp.where(hit > 0, val, qbuf)
    qcnt2 = qcnt_c + jnp.sum(sel, axis=1, keepdims=True)
    return qbuf2, qcnt2, try_c, do_c, occ_after, marked


def _store_enqueue_outs(outs, o_qbuf, o_qcnt, o_enq_try, o_do_enq, o_occ,
                        o_marked):
    qbuf2, qcnt2, enq_try, do_enq, occ_after, marked = outs
    o_qbuf[...] = qbuf2
    o_qcnt[...] = qcnt2
    o_enq_try[...] = enq_try.astype(jnp.int32)
    o_do_enq[...] = do_enq.astype(jnp.int32)
    o_occ[...] = occ_after
    o_marked[...] = marked.astype(jnp.int32)


def _queue_operands(qhead, qcnt, alive_row):
    alive = alive_row.astype(jnp.int32)
    return (_row(qhead), _col(qhead), _row(qcnt), _col(qcnt), _row(alive),
            _col(alive))


def _enqueue_kernel(qbuf_ref, qh_r, qh_c, qc_r, qc_c, al_r, al_c, apk_ref,
                    aq_c, aq_r, av_c, av_r, o_qbuf, o_qcnt, o_enq_try,
                    o_do_enq, o_occ, o_marked, *, cap, ecn_thresh):
    _store_enqueue_outs(
        _enqueue_body(qbuf_ref[...], qh_r[...], qh_c[...], qc_r[...],
                      qc_c[...], al_r[...], al_c[...], apk_ref[...],
                      aq_c[...], aq_r[...], av_c[...] != 0, av_r[...] != 0,
                      cap=cap, ecn_thresh=ecn_thresh),
        o_qbuf, o_qcnt, o_enq_try, o_do_enq, o_occ, o_marked)


def _enqueue_out_shapes(nq, cap, m):
    col = jax.ShapeDtypeStruct((m, 1), jnp.int32)
    return (jax.ShapeDtypeStruct((nq, cap), jnp.int32),
            jax.ShapeDtypeStruct((nq, 1), jnp.int32), col, col, col, col)


def _unpack_enqueue_outs(outs):
    qbuf2, qcnt2, enq_try, do_enq, occ_after, marked = outs
    return (qbuf2, qcnt2[:, 0], enq_try[:, 0] != 0, do_enq[:, 0] != 0,
            occ_after[:, 0], marked[:, 0] != 0)


@functools.partial(jax.jit, static_argnames=("cap", "ecn_thresh",
                                             "interpret"))
def enqueue(qbuf, qhead, qcnt, alive_row, apk, aq, avalid, *,
            cap, ecn_thresh, interpret: bool):
    """Fused arrival enqueue; see ``ref.enqueue``."""
    av = avalid.astype(jnp.int32)
    outs = pl.pallas_call(
        functools.partial(_enqueue_kernel, cap=cap, ecn_thresh=ecn_thresh),
        out_shape=_enqueue_out_shapes(qcnt.shape[0], cap, aq.shape[0]),
        interpret=interpret,
    )(qbuf, *_queue_operands(qhead, qcnt, alive_row), _col(apk), _col(aq),
      _row(aq), _col(av), _row(av))
    return _unpack_enqueue_outs(outs)


def _agg_jsq_enqueue_kernel(slo_ref, shi_ref, t_ref, qbuf_ref, qh_r, qh_c,
                            qc_r, qc_c, al_r, al_c, apk_c_ref, apk_r_ref,
                            aq_c_ref, aq_r_ref, ta_c_ref, ta_r_ref,
                            asw_c_ref, asw_r_ref, dead_ref, dead_t_ref,
                            pen_r_ref, pen_c_ref,
                            o_qbuf, o_qcnt, o_cfin, o_enq_try, o_do_enq,
                            o_occ, o_marked, *,
                            site, quanta, cap, ecn_thresh, off1, h):
    qcnt_r, qcnt_c = qc_r[...], qc_c[...]
    apk_c, apk_r = apk_c_ref[...], apk_r_ref[...]
    base_c = off1 + asw_c_ref[...] * h
    base_r = off1 + asw_r_ref[...] * h
    pick = functools.partial(_pick_body, s_lo=slo_ref[0, 0],
                             s_hi=shi_ref[0, 0], t=t_ref[0, 0], site=site,
                             quanta=quanta, cap=cap)
    # The pick in both orientations (bitwise-equal element-wise math), so
    # the rewritten target queue exists as a column and as a row.
    c_fin_c = pick(qcnt_r, base_c, jnp.maximum(apk_c, 0),
                   dead_ref[...] != 0, pen_r_ref[...], port_axis=1)
    c_fin_r = pick(qcnt_c, base_r, jnp.maximum(apk_r, 0),
                   dead_t_ref[...] != 0, pen_c_ref[...], port_axis=0)
    aq2_c = jnp.where(ta_c_ref[...] != 0, base_c + c_fin_c, aq_c_ref[...])
    aq2_r = jnp.where(ta_r_ref[...] != 0, base_r + c_fin_r, aq_r_ref[...])
    o_cfin[...] = c_fin_c
    _store_enqueue_outs(
        _enqueue_body(qbuf_ref[...], qh_r[...], qh_c[...], qcnt_r, qcnt_c,
                      al_r[...], al_c[...], apk_c, aq2_c, aq2_r,
                      apk_c >= 0, apk_r >= 0, cap=cap,
                      ecn_thresh=ecn_thresh),
        o_qbuf, o_qcnt, o_enq_try, o_do_enq, o_occ, o_marked)


@functools.partial(jax.jit, static_argnames=("site", "quanta", "cap",
                                             "ecn_thresh", "off1", "h",
                                             "interpret"))
def agg_jsq_enqueue(qbuf, qhead, qcnt, alive_row, apk, aq, to_agg, asw,
                    dead, pad_pen, seed_lo, seed_hi, t, *,
                    site, quanta, cap, ecn_thresh, off1, h,
                    interpret: bool):
    """Fused agg-layer JSQ pick + enqueue; see ``ref.agg_jsq_enqueue``."""
    nq, m = qcnt.shape[0], aq.shape[0]
    shapes = _enqueue_out_shapes(nq, cap, m)
    ta = to_agg.astype(jnp.int32)
    dead = dead.astype(jnp.int32)
    scalars = _scalars(seed_lo, seed_hi, t)
    ops = (qbuf, *_queue_operands(qhead, qcnt, alive_row), _col(apk),
           _row(apk), _col(aq), _row(aq), _col(ta), _row(ta), _col(asw),
           _row(asw), dead, dead.T, _row(pad_pen), _col(pad_pen))
    outs = pl.pallas_call(
        functools.partial(_agg_jsq_enqueue_kernel, site=site, quanta=quanta,
                          cap=cap, ecn_thresh=ecn_thresh, off1=off1, h=h),
        in_specs=[_SMEM] * len(scalars) + [pl.BlockSpec()] * len(ops),
        out_shape=shapes[:2] + (shapes[2],) + shapes[2:],
        interpret=interpret,
    )(*scalars, *ops)
    up = _unpack_enqueue_outs(outs[:2] + outs[3:])
    return up[:2] + (outs[2][:, 0],) + up[2:]


# ---------------------------------------------------------------------------
# SACK scoreboard: the (P,) bitmap rides as (P/128, 128) int32
# ---------------------------------------------------------------------------

def _bitmap(p_recv):
    P = p_recv.shape[0]
    pad = (-P) % _LANES
    bits = p_recv.astype(jnp.int32)
    if pad:
        bits = jnp.concatenate([bits, jnp.zeros((pad,), jnp.int32)])
    return bits.reshape(-1, _LANES)


def _window(bits, start):
    """The two bitmap rows holding packets ``start .. start + 127`` of each
    flow (``start`` an ``(F, 1)`` column), and the lane offset of
    ``start`` in the first; rows past the bitmap read 0."""
    row0 = start >> 7
    rows = _iota2((start.shape[0], bits.shape[0]), 1)
    first = _onehot_dot(rows == row0, bits)
    second = _onehot_dot(rows == row0 + 1, bits)
    return first, second, start & (_LANES - 1)


def _first_stop(first, second, off, stop, window):
    """Smallest window offset ``d`` in ``[0, window)`` at which
    ``stop(bit, d)`` holds, over the two-row view; ``window`` if none."""
    lane = _iota2(first.shape, 1)
    best = jnp.full(off.shape, window, jnp.int32)
    for bits, base in ((first, 0), (second, _LANES)):
        d = lane + base - off
        hit = (d >= 0) & (d < window) & stop(bits, d)
        best = jnp.minimum(best, jnp.min(jnp.where(hit, d, window), axis=1,
                                         keepdims=True))
    return best


def _sack_update_scan_kernel(prec_ref, pk_c_ref, pk_r_ref, dl_r_ref,
                             cum_ref, fsz_ref, pbase_ref, o_prec, o_fm, *,
                             window):
    prec = prec_ref[...]
    nrows = prec.shape[0]
    pk_c, pk_r = pk_c_ref[...], pk_r_ref[...]
    M = pk_c.shape[0]
    # Bitmap update: rows[r, i] selects lane i's packet row, at[i, c] its
    # column; any hit sets the bit (duplicates are idempotent).
    rows = ((dl_r_ref[...] != 0)
            & ((pk_r >> 7) == _iota2((nrows, M), 0))).astype(jnp.int32)
    at = ((pk_c & (_LANES - 1)) == _iota2((M, _LANES), 1)).astype(jnp.int32)
    prec2 = jnp.where(_onehot_dot(rows, at) > 0, 1, prec)
    cum = cum_ref[...]
    fsz = fsz_ref[...]
    # First missing sequence in [cum, cum + window) within the flow; the
    # oracle's window clamps to the last packet, which adds no new zero,
    # and its all-received argmin (index 0) is the w = 0 default here.
    first, second, off = _window(prec2, pbase_ref[...] + cum)
    w = _first_stop(first, second, off,
                    lambda b, d: (b == 0) & (d <= fsz - 1 - cum), window)
    w = jnp.where(w == window, 0, w)
    o_prec[...] = prec2
    o_fm[...] = jnp.minimum(cum + w, fsz - 1)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def sack_update_scan(p_recv, pk, deliv, f_cum, fsize, pbase, *,
                     interpret: bool, window=64):
    """Fused bitmap update + per-flow first-missing scan; see
    ``ref.sack_update_scan``."""
    if window > _LANES:
        raise ValueError(f"window {window} > {_LANES}: the scan reads two "
                         f"bitmap rows")
    P = p_recv.shape[0]
    bits = _bitmap(p_recv)
    F = f_cum.shape[0]
    prec2, fm = pl.pallas_call(
        functools.partial(_sack_update_scan_kernel, window=window),
        out_shape=(jax.ShapeDtypeStruct(bits.shape, jnp.int32),
                   jax.ShapeDtypeStruct((F, 1), jnp.int32)),
        interpret=interpret,
    )(bits, _col(pk), _row(pk), _row(deliv.astype(jnp.int32)), _col(f_cum),
      _col(fsize), _col(pbase))
    return prec2.reshape(-1)[:P] != 0, fm[:, 0]


def _sack_advance_kernel(prec_ref, cum_ref, fsz_ref, pbase_ref, o_cum, *,
                         rounds, window):
    prec = prec_ref[...]
    cum = cum_ref[...]
    fsz = fsz_ref[...]
    pbase = pbase_ref[...]
    for _ in range(rounds):
        # sum(cumprod(got)) is the length of the leading run of received,
        # in-flow sequences: the first offset that is missing or past the
        # flow's end.
        first, second, off = _window(prec, pbase + cum)
        adv = _first_stop(first, second, off,
                          lambda b, d: (b == 0) | (d >= fsz - cum), window)
        cum = jnp.minimum(cum + adv, fsz)
    o_cum[...] = cum


@functools.partial(jax.jit, static_argnames=("rounds", "window",
                                             "interpret"))
def sack_advance(p_recv, f_cum, fsize, pbase, *, interpret: bool, rounds=2,
                 window=4):
    """Fused cumulative-ack advance rounds; see ``ref.sack_advance``."""
    if window > _LANES:
        raise ValueError(f"window {window} > {_LANES}: the scan reads two "
                         f"bitmap rows")
    out = pl.pallas_call(
        functools.partial(_sack_advance_kernel, rounds=rounds,
                          window=window),
        out_shape=jax.ShapeDtypeStruct((f_cum.shape[0], 1), jnp.int32),
        interpret=interpret,
    )(_bitmap(p_recv), _col(f_cum), _col(fsize), _col(pbase))
    return out[:, 0]
