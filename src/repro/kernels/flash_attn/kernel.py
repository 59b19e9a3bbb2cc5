"""Pallas TPU kernel: causal GQA flash attention (forward).

Design (TPU-native, not a CUDA port):
  * grid = (batch, q_heads, Sq // block_q): one program per query tile;
  * the query tile (block_q, D) lives in VMEM; K/V for the *kv head* of this
    query head (GQA mapping done in the BlockSpec index_map) are staged in
    VMEM as (Sk, D) blocks -- sized for Sk*D*4B <= a few MB, i.e. contexts up
    to ~8k at D=128.  Longer contexts tile over an extra kv grid dimension at
    the ops layer (chunked attention with softmax recombination);
  * inner fori_loop walks kv tiles of size block_k with the online-softmax
    (m, l, acc) recurrence; the causal tile skip bounds the loop count so the
    average program does half the work (the scheduler-visible win of
    causality);
  * matmul tiles are (block_q x D) @ (D x block_k) -> MXU-aligned when
    block_q, block_k, D are multiples of 128 (D=64 also lowers fine).

Validated on CPU with interpret=True against ``ref.mha``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1.0e30  # python float (jnp scalars become captured consts)


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *, scale, block_k, causal,
                 sk_total, q_offset):
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, D)
    bq = q.shape[0]
    n_kv = sk_total // block_k
    # causal limit: last kv tile that any query in this tile can see
    if causal:
        q_last = q_offset + qi * bq + bq - 1
        kv_hi = jnp.minimum((q_last // block_k) + 1, n_kv)
    else:
        kv_hi = n_kv

    def body(j, carry):
        m, l, acc = carry
        k = jax.lax.dynamic_slice_in_dim(
            k_ref[0, 0], j * block_k, block_k).astype(jnp.float32)
        v = jax.lax.dynamic_slice_in_dim(
            v_ref[0, 0], j * block_k, block_k).astype(jnp.float32)
        s = q @ k.T                                        # (bq, bk)
        if causal:
            qpos = q_offset + qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 0)
            kpos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=1)
        acc_new = acc * alpha[:, None] + p @ v
        return m_new, l_new, acc_new

    m0 = jnp.full((bq,), NEG_INF)
    l0 = jnp.zeros((bq,))
    acc0 = jnp.zeros((bq, q.shape[1]))
    m, l, acc = jax.lax.fori_loop(0, kv_hi, body, (m0, l0, acc0))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret", "scale"))
def flash_attention(q, k, v, *, interpret: bool, causal: bool = True,
                    scale=None, block_q: int = 128, block_k: int = 128):
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Sk, D). Returns (B, Hq, Sq, D).

    For decode (Sq < block_q) the q tile shrinks to Sq.  Queries are assumed
    to occupy the last Sq positions of the Sk-long context (KV-cache layout).
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    assert Hq % Hkv == 0
    group = Hq // Hkv
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    block_q = min(block_q, Sq)
    # pad Sq to a block multiple
    pq = (-Sq) % block_q
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    pk = (-Sk) % block_k
    if pk:
        # pad keys with zeros; mask via causal bound won't see them for
        # causal=True; for non-causal we mask explicitly below by padding
        # k with NEG-scoring values: simplest is to require Sk % block_k == 0
        raise ValueError(f"Sk={Sk} must be a multiple of block_k={block_k}")
    Sq_p = q.shape[2]
    q_offset = Sk - Sq          # causal alignment for KV-cache decode

    kernel = functools.partial(
        _attn_kernel, scale=scale, block_k=block_k, causal=causal,
        sk_total=Sk, q_offset=q_offset)
    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, Sq_p // block_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, Sk, D), lambda b, h, i: (b, h // group, 0, 0)),
            pl.BlockSpec((1, 1, Sk, D), lambda b, h, i: (b, h // group, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq_p, D), q.dtype),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :Sq]
