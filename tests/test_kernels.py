"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracles,
swept over shapes/dtypes, plus hypothesis property tests."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:            # fall back to a deterministic sample sweep
    from _hyp_fallback import given, settings, st

from repro.kernels.lindley import kernel as lk, ref as lr, ops as lo
from repro.kernels.flash_attn import kernel as fk, ref as fr, ops as fo
from repro.kernels.ssd_scan import kernel as sk, ref as sr, ops as so


# ---------------------------------------------------------------------------
# lindley segmented max-plus scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 7, 256, 1000, 4096])
@pytest.mark.parametrize("block", [128, 1024])
def test_lindley_kernel_matches_oracle(n, block, rng):
    v = rng.normal(size=n).astype(np.float32) * 100
    f = rng.random(n) < 0.15
    f[0] = True
    out_k = np.asarray(lk.segmented_cummax(jnp.asarray(v), jnp.asarray(f),
                                           block=block, interpret=True))
    out_r = np.asarray(lr.segmented_cummax(jnp.asarray(v), jnp.asarray(f)))
    np.testing.assert_allclose(out_k, out_r)


@given(st.integers(1, 300), st.integers(0, 2**31 - 1))
@settings(max_examples=30, deadline=None)
def test_lindley_property_vs_serial(n, seed):
    r = np.random.default_rng(seed)
    v = r.normal(size=n).astype(np.float32)
    f = r.random(n) < 0.3
    f[0] = True
    out = np.asarray(lr.segmented_cummax(jnp.asarray(v), jnp.asarray(f)))
    ser = lr.segmented_cummax_serial(v, f)
    np.testing.assert_allclose(out, ser)


def test_lindley_departures_are_fifo_and_causal(rng):
    """Property: departures are strictly increasing within a queue and never
    precede arrival + service."""
    n = 500
    a = np.sort(rng.uniform(0, 100, n)).astype(np.float32)
    seg = np.zeros(n, bool)
    seg[0] = True
    seg[rng.choice(np.arange(1, n), 20, replace=False)] = True
    d = np.asarray(lo.lindley_departures(jnp.asarray(a), jnp.asarray(seg)))
    start = 0
    for i in range(1, n + 1):
        if i == n or seg[i]:
            dd = d[start:i]
            aa = a[start:i]
            assert (np.diff(dd) >= 1.0 - 1e-3).all()     # 1 pkt/slot service
            assert (dd >= aa + 1.0 - 1e-3).all()          # causality (f32)
            start = i


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

SHAPES = [
    (1, 4, 2, 128, 128, 64),
    (2, 8, 8, 256, 256, 64),
    (1, 8, 1, 128, 128, 128),
    (1, 4, 4, 1, 256, 64),      # decode
    (2, 6, 2, 64, 256, 32),     # Sq < Sk (query tail)
]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(shape, dtype, rng):
    B, Hq, Hkv, Sq, Sk, D = shape
    q = jnp.asarray(rng.normal(size=(B, Hq, Sq, D)), dtype)
    k = jnp.asarray(rng.normal(size=(B, Hkv, Sk, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, Hkv, Sk, D)), dtype)
    out_k = fk.flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                               interpret=True)
    out_r = fr.mha(q, k, v, causal=True)
    tol = 2e-5 if dtype == np.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32),
                               atol=tol, rtol=tol)


def test_chunked_matches_full(rng):
    B, Hq, Hkv, S, D = 1, 4, 2, 512, 64
    q = jnp.asarray(rng.normal(size=(B, Hq, S, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Hkv, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Hkv, S, D)), jnp.float32)
    full = fr.mha(q, k, v, causal=True)
    chunk = fr.mha_chunked(q, k, v, causal=True, block_k=128)
    np.testing.assert_allclose(np.asarray(full), np.asarray(chunk),
                               atol=2e-5, rtol=2e-5)


def test_chunked_mixed_dims(rng):
    """MLA shape: d_k=48, d_v=32."""
    q = jnp.asarray(rng.normal(size=(1, 4, 64, 48)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 4, 64, 48)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 4, 64, 32)), jnp.float32)
    out = fr.mha_chunked(q, k, v, causal=True, block_k=32)
    # oracle: dense softmax
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(48)
    mask = jnp.tril(jnp.ones((64, 64), bool))
    logits = jnp.where(mask[None, None], logits, -1e30)
    p = jax.nn.softmax(logits, -1)
    ref = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@given(st.integers(1, 4), st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_attention_rowsum_property(heads, seed):
    """Attention outputs are convex combinations of V rows: with identical V
    rows the output equals that row (softmax sums to 1)."""
    r = np.random.default_rng(seed)
    q = jnp.asarray(r.normal(size=(1, heads, 32, 16)), jnp.float32)
    k = jnp.asarray(r.normal(size=(1, heads, 32, 16)), jnp.float32)
    row = r.normal(size=(16,)).astype(np.float32)
    v = jnp.broadcast_to(jnp.asarray(row), (1, heads, 32, 16))
    out = fr.mha(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.broadcast_to(row, out.shape),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# Mamba2 SSD scan
# ---------------------------------------------------------------------------

SSD_SHAPES = [
    (1, 64, 2, 16, 1, 16),
    (2, 128, 4, 32, 2, 64),
    (1, 96, 8, 64, 4, 32),    # L not multiple of 64 (ops pads)
]


@pytest.mark.parametrize("shape", SSD_SHAPES)
def test_ssd_kernel_and_chunked_match_scan(shape, rng):
    B, L, H, P, G, N = shape
    x = jnp.asarray(rng.normal(size=(B, L, H, P)), jnp.float32)
    dt = jnp.asarray(0.01 + rng.random((B, L, H)) * 0.2, jnp.float32)
    A = jnp.asarray(-0.5 - rng.random(H), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, L, G, N)), jnp.float32)
    C = jnp.asarray(rng.normal(size=(B, L, G, N)), jnp.float32)
    oracle = np.asarray(sr.ssd_scan(x, dt, A, Bm, C))
    chunked = np.asarray(so.ssd(x, dt, A, Bm, C, chunk=32,
                                backend="chunked"))
    np.testing.assert_allclose(chunked, oracle, atol=5e-5, rtol=5e-4)
    if L % 32 == 0:
        pallas = np.asarray(sk.ssd_scan(x, dt, A, Bm, C, chunk=32,
                                        interpret=True))
        np.testing.assert_allclose(pallas, oracle, atol=5e-5, rtol=5e-4)


def test_ssd_final_state_matches_sequential(rng):
    B, L, H, P, G, N = 1, 48, 2, 8, 1, 8
    x = jnp.asarray(rng.normal(size=(B, L, H, P)), jnp.float32)
    dt = jnp.asarray(0.05 + rng.random((B, L, H)) * 0.1, jnp.float32)
    A = jnp.asarray(-1.0 - rng.random(H), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, L, G, N)), jnp.float32)
    C = jnp.asarray(rng.normal(size=(B, L, G, N)), jnp.float32)
    hf = np.asarray(sr.ssd_final_state(x, dt, A, Bm, C, chunk=16))
    # sequential oracle
    h = np.zeros((B, H, N, P), np.float32)
    xn, dtn, An = map(np.asarray, (x, dt, A))
    Bn = np.repeat(np.asarray(Bm), H // G, axis=2)
    for t in range(L):
        for b in range(B):
            for hh in range(H):
                h[b, hh] = (np.exp(An[hh] * dtn[b, t, hh]) * h[b, hh]
                            + dtn[b, t, hh]
                            * np.outer(Bn[b, t, hh], xn[b, t, hh]))
    np.testing.assert_allclose(hf, h, atol=1e-4, rtol=1e-3)


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_ssd_decay_property(seed):
    """With A -> -inf (instant forgetting) the SSD reduces to the per-step
    readout C_t . (dt_t B_t x_t^T)."""
    r = np.random.default_rng(seed)
    B, L, H, P, G, N = 1, 16, 1, 4, 1, 4
    x = jnp.asarray(r.normal(size=(B, L, H, P)), jnp.float32)
    dt = jnp.asarray(np.full((B, L, H), 1.0), jnp.float32)
    A = jnp.asarray([-50.0], jnp.float32)
    Bm = jnp.asarray(r.normal(size=(B, L, G, N)), jnp.float32)
    C = jnp.asarray(r.normal(size=(B, L, G, N)), jnp.float32)
    y = np.asarray(sr.ssd_scan(x, dt, A, Bm, C))
    expect = np.einsum("blgn,blgn,blhp->blhp",
                       np.asarray(C), np.asarray(Bm), np.asarray(x))
    np.testing.assert_allclose(y, expect, atol=1e-4, rtol=1e-3)


# ---------------------------------------------------------------------------
# slot-step kernels (JSQ port-rank + enqueue, SACK scoreboard scans)
# ---------------------------------------------------------------------------

import functools  # noqa: E402

from repro.core import entropy as ent  # noqa: E402
from repro.kernels.slot_step import (  # noqa: E402
    kernel as qk, ref as qr, ops as qo)

_Q = dict(m=24, h=8, nq=48, cap=8, f=12, per_flow=16, off1=8, n_aggs=4)


def _slot_operands(seed, m=_Q["m"], h=_Q["h"], nq=_Q["nq"], cap=_Q["cap"],
                   f=_Q["f"], per_flow=_Q["per_flow"]):
    """Random engine-shaped operands for one slot step."""
    r = np.random.default_rng(seed)
    p = f * per_flow
    o = dict(
        qcnt=jnp.asarray(r.integers(0, cap, nq), jnp.int32),
        qbuf=jnp.asarray(r.integers(-1, p, (nq, cap)), jnp.int32),
        qhead=jnp.asarray(r.integers(0, cap, nq), jnp.int32),
        qbase=jnp.asarray(r.integers(0, nq - h, m), jnp.int32),
        ids=jnp.asarray(r.integers(0, p, m), jnp.int32),
        dead=jnp.asarray(r.random((m, h)) < 0.2),
        pad_pen=jnp.where(jnp.arange(h) < h - 2, 0.0,
                          1e9).astype(jnp.float32),
        alive=jnp.asarray(r.random(nq) < 0.9),
        apk=jnp.asarray(np.where(r.random(m) < 0.8,
                                 r.integers(0, p, m), -1), jnp.int32),
        aq=jnp.asarray(r.integers(0, nq, m), jnp.int32),
        asw=jnp.asarray(r.integers(0, _Q["n_aggs"], m), jnp.int32),
        p_recv=jnp.asarray(r.random(p) < 0.5),
        pk=jnp.asarray(r.integers(0, p, m), jnp.int32),
        deliv=jnp.asarray(r.random(m) < 0.5),
        f_cum=jnp.asarray(r.integers(0, per_flow, f), jnp.int32),
        fsize=jnp.full((f,), per_flow, jnp.int32),
        pbase=jnp.arange(f, dtype=jnp.int32) * per_flow,
        seed_lo=jnp.uint32(r.integers(0, 2**32)),
        seed_hi=jnp.uint32(r.integers(0, 2**32)),
        t=jnp.int32(r.integers(0, 4000)),
    )
    o["avalid"] = o["apk"] >= 0
    o["to_agg"] = o["avalid"] & (r.random(m) < 0.5)
    # aq of agg-bound lanes is rewritten by the pick; keep others in range
    return o


def _jsq_args(o):
    return (o["qcnt"], o["qbase"], o["ids"], o["dead"], o["pad_pen"],
            o["seed_lo"], o["seed_hi"], o["t"])


@pytest.mark.parametrize("quanta", [None, (0.05, 0.10, 0.20)])
@pytest.mark.parametrize("block", [None, 7, 16])
def test_slot_jsq_pick_matches_ref(quanta, block):
    """Interpret-mode JSQ pick is bitwise the oracle, including tile tails
    that don't divide the chooser count (block=7 over 24 lanes pads)."""
    o = _slot_operands(1)
    kw = dict(site=ent.SITE_EDGE_JSQ, quanta=quanta, cap=_Q["cap"])
    got = qk.jsq_pick(*_jsq_args(o), block=block, interpret=True, **kw)
    want = qr.jsq_pick(*_jsq_args(o), **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_slot_jsq_padded_ports_never_picked():
    """port_pad_penalty masking: lanes past the real port count carry a 1e9
    penalty, so no pick may land there (unless every port is padded)."""
    o = _slot_operands(2)
    o["dead"] = jnp.zeros_like(o["dead"])     # only the pad penalty acts
    kw = dict(site=ent.SITE_EDGE_JSQ, quanta=None, cap=_Q["cap"])
    for backend in ("xla", "pallas"):
        pick = qo.jsq_pick(*_jsq_args(o), backend=backend, **kw)
        assert (np.asarray(pick) < _Q["h"] - 2).all(), backend


def test_slot_enqueue_matches_ref():
    o = _slot_operands(3)
    kw = dict(cap=_Q["cap"], ecn_thresh=5)
    got = qk.enqueue(o["qbuf"], o["qhead"], o["qcnt"], o["alive"],
                     o["apk"], o["aq"], o["avalid"], interpret=True, **kw)
    want = qr.enqueue(o["qbuf"], o["qhead"], o["qcnt"], o["alive"],
                      o["apk"], o["aq"], o["avalid"], **kw)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("quanta", [None, (0.05, 0.10, 0.20)])
def test_slot_agg_jsq_enqueue_matches_ref(quanta):
    o = _slot_operands(4)
    kw = dict(site=ent.SITE_AGG_JSQ, quanta=quanta, cap=_Q["cap"],
              ecn_thresh=5, off1=_Q["off1"], h=_Q["h"])
    args = (o["qbuf"], o["qhead"], o["qcnt"], o["alive"], o["apk"],
            o["aq"], o["to_agg"], o["asw"], o["dead"], o["pad_pen"],
            o["seed_lo"], o["seed_hi"], o["t"])
    got = qk.agg_jsq_enqueue(*args, interpret=True, **kw)
    want = qr.agg_jsq_enqueue(*args, **kw)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_slot_sack_scans_match_ref():
    o = _slot_operands(5)
    got = qk.sack_update_scan(o["p_recv"], o["pk"], o["deliv"], o["f_cum"],
                              o["fsize"], o["pbase"], interpret=True)
    want = qr.sack_update_scan(o["p_recv"], o["pk"], o["deliv"], o["f_cum"],
                               o["fsize"], o["pbase"])
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    ga = qk.sack_advance(o["p_recv"], o["f_cum"], o["fsize"], o["pbase"],
                         interpret=True)
    wa = qr.sack_advance(o["p_recv"], o["f_cum"], o["fsize"], o["pbase"])
    np.testing.assert_array_equal(np.asarray(ga), np.asarray(wa))


def test_slot_kernels_campaign_batch_dim():
    """The fused campaign axis rides vmap's leading batch dim (>= 2 rows):
    batched kernel outputs equal the per-row oracle row-for-row."""
    rows = [_slot_operands(10 + i) for i in range(3)]
    stack = {k: jnp.stack([o[k] for o in rows]) for k in rows[0]}
    kw = dict(site=ent.SITE_EDGE_JSQ, quanta=None, cap=_Q["cap"])
    k_fn = jax.vmap(functools.partial(qk.jsq_pick, interpret=True, **kw))
    picks = k_fn(stack["qcnt"], stack["qbase"], stack["ids"], stack["dead"],
                 stack["pad_pen"], stack["seed_lo"], stack["seed_hi"],
                 stack["t"])
    for i, o in enumerate(rows):
        np.testing.assert_array_equal(np.asarray(picks[i]),
                                      np.asarray(qr.jsq_pick(*_jsq_args(o),
                                                             **kw)))
    e_fn = jax.vmap(functools.partial(qk.enqueue, cap=_Q["cap"],
                                      ecn_thresh=5, interpret=True))
    outs = e_fn(stack["qbuf"], stack["qhead"], stack["qcnt"], stack["alive"],
                stack["apk"], stack["aq"], stack["avalid"])
    for i, o in enumerate(rows):
        want = qr.enqueue(o["qbuf"], o["qhead"], o["qcnt"], o["alive"],
                          o["apk"], o["aq"], o["avalid"], cap=_Q["cap"],
                          ecn_thresh=5)
        for g, w in zip(outs, want):
            np.testing.assert_array_equal(np.asarray(g[i]), np.asarray(w))
    s_fn = jax.vmap(functools.partial(qk.sack_update_scan, interpret=True))
    prec, fm = s_fn(stack["p_recv"], stack["pk"], stack["deliv"],
                    stack["f_cum"], stack["fsize"], stack["pbase"])
    for i, o in enumerate(rows):
        wr, wf = qr.sack_update_scan(o["p_recv"], o["pk"], o["deliv"],
                                     o["f_cum"], o["fsize"], o["pbase"])
        np.testing.assert_array_equal(np.asarray(prec[i]), np.asarray(wr))
        np.testing.assert_array_equal(np.asarray(fm[i]), np.asarray(wf))


def test_slot_ops_backend_switch():
    """ops-layer contract: bad backends raise, resolve_impl honors the
    REPRO_PALLAS=interpret CI override, xla == pallas bitwise."""
    o = _slot_operands(6)
    kw = dict(site=ent.SITE_EDGE_JSQ, quanta=None, cap=_Q["cap"])
    with pytest.raises(ValueError):
        qo.jsq_pick(*_jsq_args(o), backend="nope", **kw)
    with pytest.raises(ValueError):
        qo.resolve_impl("nope")
    assert qo.resolve_impl("lax") == "lax"
    assert qo.resolve_impl("pallas") == "pallas"
    import os as _os
    forced = _os.environ.get("REPRO_PALLAS", "") == "interpret"
    on_tpu = jax.default_backend() == "tpu"
    assert qo.resolve_impl("auto") == (
        "pallas" if (on_tpu or forced) else "lax")
    np.testing.assert_array_equal(
        np.asarray(qo.jsq_pick(*_jsq_args(o), backend="xla", **kw)),
        np.asarray(qo.jsq_pick(*_jsq_args(o), backend="pallas", **kw)))
