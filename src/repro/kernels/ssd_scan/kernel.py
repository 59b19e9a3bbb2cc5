"""Pallas TPU kernel: Mamba2 SSD chunked scan.

TPU mapping of the state-space-duality algorithm:

  * grid = (batch, heads): one program owns a full (L, P) sequence for one
    head -- the sequential chunk recurrence stays inside the program, so the
    state (N, P) never leaves VMEM/registers;
  * per chunk of Q steps, the three terms are dense matmuls on the MXU:
      intra:  (Q,N)@(N,Q) decay-masked, then (Q,Q)@(Q,P)
      inter:  (Q,N)@(N,P)
      state:  (N,Q)@(Q,P)
  * Q and N default to 64/128: MXU-aligned; P (head dim) 64.

Grouped B/C (the Mamba2 analogue of GQA) is resolved in the BlockSpec
index_map, exactly like kv heads in flash attention.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, o_ref, *, chunk, n_state):
    L = x_ref.shape[2]
    P = x_ref.shape[3]
    Q = chunk
    a = a_ref[0, 0]

    x = x_ref[0, 0].astype(jnp.float32)       # (L, P)
    dtv = dt_ref[0, 0].astype(jnp.float32)    # (L,)
    Bm = b_ref[0, 0].astype(jnp.float32)      # (L, N)
    Cm = c_ref[0, 0].astype(jnp.float32)      # (L, N)

    mask = jnp.tril(jnp.ones((Q, Q), jnp.float32))

    def body(ci, carry):
        h = carry                              # (N, P)
        sl = ci * Q
        xq = jax.lax.dynamic_slice_in_dim(x, sl, Q)
        dq = jax.lax.dynamic_slice_in_dim(dtv, sl, Q)
        Bq = jax.lax.dynamic_slice_in_dim(Bm, sl, Q)
        Cq = jax.lax.dynamic_slice_in_dim(Cm, sl, Q)
        lam = jnp.cumsum(a * dq)               # (Q,)
        dec = jnp.exp(lam[:, None] - lam[None, :]) * mask
        S = (Cq @ Bq.T) * dec * dq[None, :]
        y_intra = S @ xq                        # (Q, P)
        y_inter = jnp.exp(lam)[:, None] * (Cq @ h)
        o_slice = (y_intra + y_inter).astype(o_ref.dtype)
        o_ref[0, 0, pl.ds(sl, Q), :] = o_slice
        w = jnp.exp(lam[-1] - lam) * dq         # (Q,)
        h_new = jnp.exp(lam[-1]) * h + (Bq * w[:, None]).T @ xq
        return h_new

    h0 = jnp.zeros((n_state, P), jnp.float32)
    jax.lax.fori_loop(0, L // Q, body, h0)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, B_mat, C, *, interpret: bool, chunk: int = 64):
    """x (B,L,H,P); dt (B,L,H); A (H,); B_mat/C (B,L,G,N). Returns (B,L,H,P).

    L must be a multiple of ``chunk`` (the ops wrapper pads).
    """
    Bsz, L, H, P = x.shape
    G, N = B_mat.shape[2], B_mat.shape[3]
    assert H % G == 0 and L % chunk == 0
    group = H // G
    # layout: (B, H, L, P) etc. so each program gets contiguous blocks
    xt = jnp.moveaxis(x, 2, 1)                   # (B,H,L,P)
    dtt = jnp.moveaxis(dt, 2, 1)                 # (B,H,L)
    Bt = jnp.moveaxis(B_mat, 2, 1)               # (B,G,L,N)
    Ct = jnp.moveaxis(C, 2, 1)
    A2 = jnp.broadcast_to(A.astype(jnp.float32), (Bsz, H))

    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_state=N)
    out = pl.pallas_call(
        kernel,
        grid=(Bsz, H),
        in_specs=[
            pl.BlockSpec((1, 1, L, P), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, L), lambda b, h: (b, h, 0)),
            pl.BlockSpec((1, 1), lambda b, h: (b, h)),
            pl.BlockSpec((1, 1, L, N), lambda b, h: (b, h // group, 0, 0)),
            pl.BlockSpec((1, 1, L, N), lambda b, h: (b, h // group, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, L, P), lambda b, h: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((Bsz, H, L, P), x.dtype),
        interpret=interpret,
    )(xt, dtt, A2, Bt, Ct)
    return jnp.moveaxis(out, 1, 2)
