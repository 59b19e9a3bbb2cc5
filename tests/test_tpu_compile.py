"""Ahead-of-time compiles of the simulator's Pallas kernels for a TPU v5e.

Interpret mode (the CPU path every other kernel test runs) accepts any
gather, scatter or layout; the TPU kernel compiler (Mosaic) does not.  Each
test here compiles one kernel with ``interpret=False`` for a described, not
attached, v5e chip at the paper's k=8 shapes -- vmapped over a fused
campaign axis, as the engines call it -- and checks that the kernel is in
the compiled program.  Nothing runs, so results are not checked here.

The topology is described inside a fixture: only one process at a time may
load the TPU library, so no module may touch it at import time.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import entropy as ent
from repro.kernels.lindley import kernel as lk
from repro.kernels.slot_step import kernel as sk
from repro.net.loopsim import LoopConfig
from repro.net.topology import FatTree

ROWS = 2                      # fused campaign rows (the vmapped axis)
QUANTA = (0.05, 0.10, 0.20)   # switch_pkt_ar's jsq_quant thresholds


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def shapes():
    """k=8 loop-engine shapes of fig12 (permutation of 256 packets)."""
    tree = FatTree(8)
    n, mid = tree.n_hosts, tree.queues_per_mid_layer
    nq = 4 * mid + n
    return dict(n=n, h=tree.half, nq=nq, m=nq, cap=LoopConfig().buffer_pkts,
                f=n, p=n * 256, n_aggs=tree.k * tree.half)


def _compile(fn, one_chip, *avals):
    """Compile ``vmap(fn)`` over ROWS for the described chip; returns the
    compiled program's text."""
    args = [jax.ShapeDtypeStruct((ROWS,) + shape, dtype, sharding=one_chip)
            for shape, dtype in avals]
    compiled = jax.jit(jax.vmap(fn)).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    return compiled


I32, U32, F32, B = jnp.int32, jnp.uint32, jnp.float32, jnp.bool_


@pytest.mark.parametrize("quanta", [None, QUANTA])
def test_jsq_pick_compiles(one_chip, no_compile_cache, shapes, quanta):
    s = shapes
    fn = functools.partial(sk.jsq_pick, site=ent.SITE_EDGE_JSQ,
                           quanta=quanta, cap=s["cap"], interpret=False)
    _compile(fn, one_chip, ((s["nq"],), I32), ((s["n"],), I32),
             ((s["n"],), I32), ((s["n"], s["h"]), B), ((s["h"],), F32),
             ((), U32), ((), U32), ((), I32))


def test_enqueue_compiles(one_chip, no_compile_cache, shapes):
    s = shapes
    fn = functools.partial(sk.enqueue, cap=s["cap"], ecn_thresh=97,
                           interpret=False)
    _compile(fn, one_chip, ((s["nq"], s["cap"]), I32), ((s["nq"],), I32),
             ((s["nq"],), I32), ((s["nq"],), B), ((s["m"],), I32),
             ((s["m"],), I32), ((s["m"],), B))


@pytest.mark.parametrize("quanta", [None, QUANTA])
def test_agg_jsq_enqueue_compiles(one_chip, no_compile_cache, shapes,
                                  quanta):
    s = shapes
    fn = functools.partial(sk.agg_jsq_enqueue, site=ent.SITE_AGG_JSQ,
                           quanta=quanta, cap=s["cap"], ecn_thresh=97,
                           off1=s["nq"] // 5, h=s["h"], interpret=False)
    m = s["m"]
    _compile(fn, one_chip, ((s["nq"], s["cap"]), I32), ((s["nq"],), I32),
             ((s["nq"],), I32), ((s["nq"],), B), ((m,), I32), ((m,), I32),
             ((m,), B), ((m,), I32), ((m, s["h"]), B), ((s["h"],), F32),
             ((), U32), ((), U32), ((), I32))


def test_sack_update_scan_compiles(one_chip, no_compile_cache, shapes):
    s = shapes
    fn = functools.partial(sk.sack_update_scan, interpret=False)
    _compile(fn, one_chip, ((s["p"],), B), ((s["m"],), I32),
             ((s["m"],), B), ((s["f"],), I32), ((s["f"],), I32),
             ((s["f"],), I32))


def test_sack_advance_compiles(one_chip, no_compile_cache, shapes):
    s = shapes
    fn = functools.partial(sk.sack_advance, interpret=False)
    _compile(fn, one_chip, ((s["p"],), B), ((s["f"],), I32),
             ((s["f"],), I32), ((s["f"],), I32))


def test_lindley_scan_compiles(one_chip, no_compile_cache, shapes):
    """The fast engine's scan over one k=8 permutation dispatch's packets."""
    fn = functools.partial(lk.segmented_cummax, interpret=False)
    _compile(fn, one_chip, ((shapes["p"],), F32), ((shapes["p"],), B))
