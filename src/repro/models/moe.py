"""Mixture-of-Experts layer with explicit expert-parallel dispatch.

This is where the paper's technique lands in the trainer: the EP dispatch is
an **AllToAll across the model axis**, and the paper (§2, §5) treats MoE
training traffic as exactly this collective.  Three implementations:

  * ``dense``    -- every expert on every token (tiny smoke configs; oracle);
  * ``a2a``      -- shard_map with ``jax.lax.all_to_all`` (XLA's native
                    collective; on the DCN this is what hash-based fabric LB
                    must carry in one shot);
  * ``rotation`` -- shard_map with the (n-1)-round **destination rotation**
                    decomposition via ``ppermute`` (the DR discipline of the
                    paper applied at the collective layer: every round is a
                    permutation, per-destination balanced).

Capacity-factor token dropping (standard production MoE) bounds buffer
shapes; dropped tokens pass through the residual stream.

Token layout inside shard_map: batch sharded over (pod, data), sequence
sharded over model (classic DeepSpeed-MoE EP+SP), experts sharded over model,
expert weights additionally FSDP-sharded over data and all-gathered on use
(ZeRO-3 style; the gather's transpose is a reduce-scatter in backward).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import layers as L
from . import sharding as sh


def param_shapes(cfg, n_moe_layers: int):
    d = L.dtype_of(cfg)
    D, E, F = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    sd = jax.ShapeDtypeStruct
    p = {
        "router": sd((n_moe_layers, D, E), jnp.float32),
        "w_gate": sd((n_moe_layers, E, D, F), d),
        "w_up": sd((n_moe_layers, E, D, F), d),
        "w_down": sd((n_moe_layers, E, F, D), d),
    }
    if cfg.n_shared_experts:
        Fs = F * cfg.n_shared_experts
        p.update({"ws_gate": sd((n_moe_layers, D, Fs), d),
                  "ws_up": sd((n_moe_layers, D, Fs), d),
                  "ws_down": sd((n_moe_layers, Fs, D), d)})
    return p


def _route(x2d, router, k):
    """x2d (T, D) -> (gates (T,k) fp32, experts (T,k) int32)."""
    logits = x2d.astype(jnp.float32) @ router
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, idx.astype(jnp.int32)


def _seg_rank(sorted_keys):
    n = sorted_keys.shape[0]
    idx = jnp.arange(n, dtype=jnp.float32)
    flag = jnp.concatenate([jnp.ones((1,), bool),
                            sorted_keys[1:] != sorted_keys[:-1]])
    start = jax.lax.associative_scan(
        lambda a, b: (jnp.where(b[1], b[0], jnp.maximum(a[0], b[0])),
                      a[1] | b[1]),
        (jnp.where(flag, idx, -1.0), flag))[0]
    return (idx - start).astype(jnp.int32)


def _dispatch(x2d, gates, experts, E, C):
    """Scatter tokens into per-expert capacity buffers.

    Returns (buf (E, C, D), gate_buf (E, C), tok_buf (E, C) token index or -1).
    """
    T, k = experts.shape
    flat_e = experts.reshape(-1)
    flat_g = gates.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    order = jnp.argsort(flat_e, stable=True)
    se, sg, stk = flat_e[order], flat_g[order], flat_t[order]
    rank = _seg_rank(se)
    keep = rank < C
    row = jnp.where(keep, se, E)
    col = jnp.where(keep, rank, 0)
    D = x2d.shape[1]
    buf = jnp.zeros((E, C, D), x2d.dtype).at[row, col].set(
        x2d[stk], mode="drop")
    gate_buf = jnp.zeros((E, C), jnp.float32).at[row, col].set(
        sg, mode="drop")
    tok_buf = jnp.full((E, C), -1, jnp.int32).at[row, col].set(
        stk, mode="drop")
    return buf, gate_buf, tok_buf


def _expert_mlp(buf, wg, wu, wd):
    """buf (E, C, D); weights (E, D, F)/(E, F, D)."""
    g = jnp.einsum("ecd,edf->ecf", buf, wg)
    u = jnp.einsum("ecd,edf->ecf", buf, wu)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(buf.dtype) * u
    return jnp.einsum("ecf,efd->ecd", h, wd)


def _expert_mlp_zero3(buf, wg, wu, wd, fsdp_ax="data", unroll=False):
    """Scan over local experts, gathering ONE expert's FSDP-sharded weights
    at a time (live set = one expert's weights, ~90 MB for DeepSeek-V3,
    instead of all E_loc experts at once -- the difference between fitting
    and not fitting the 61-layer config in HBM).

    buf (E_loc, C, D); wg/wu (E_loc, D_shard, F); wd (E_loc, F, D_shard).
    """
    def body(_, xs):
        x_e, wg_e, wu_e, wd_e = xs
        wg_f = jax.lax.all_gather(wg_e, fsdp_ax, axis=0, tiled=True)
        wu_f = jax.lax.all_gather(wu_e, fsdp_ax, axis=0, tiled=True)
        wd_f = jax.lax.all_gather(wd_e, fsdp_ax, axis=1, tiled=True)
        g = x_e @ wg_f
        u = x_e @ wu_f
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x_e.dtype) * u
        return None, h @ wd_f
    _, ys = jax.lax.scan(body, None, (buf, wg, wu, wd), unroll=unroll)
    return ys


def _a2a(x, axis, *, split, concat, impl, axis_size):
    """AllToAll over a mesh axis: XLA native, or the paper's DR rotation.

    Rotation (destination-based rotation at the collective layer): n-1
    ``ppermute`` rounds; in round r every shard sends the chunk destined to
    peer (me+r) -- a pure permutation per round, so every link carries
    exactly one chunk (the Theta(1)-queue discipline of §6-7 mapped onto the
    collective schedule)."""
    if impl != "rotation" or axis_size == 1:
        return jax.lax.all_to_all(x, axis, split_axis=split,
                                  concat_axis=concat, tiled=True)
    n = axis_size
    me = jax.lax.axis_index(axis)
    chunks = jnp.stack(jnp.split(x, n, axis=split), axis=0)  # (n, ...)
    out_shape = list(chunks.shape[1:])
    out_shape[concat] *= n
    out = jnp.zeros(out_shape, x.dtype)
    csz = chunks.shape[1:][concat]

    def put(arr, block, pos):
        start = [0] * arr.ndim
        start[concat] = pos * csz
        return jax.lax.dynamic_update_slice(arr, block, tuple(start))

    # own chunk: tiled-a2a layout puts data received from peer j at slot j.
    out = put(out, jnp.take(chunks, me, axis=0), me)
    for r in range(1, n):
        send = jnp.take(chunks, (me + r) % n, axis=0)
        recv = jax.lax.ppermute(send, axis,
                                [(i, (i + r) % n) for i in range(n)])
        out = put(out, recv, (me - r) % n)
    return out


def moe_block(cfg, p, x, *, impl: Optional[str] = None):
    """x (B, S, D) -> (B, S, D).  Routed experts + optional shared expert."""
    impl = impl or cfg.moe_impl
    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.experts_per_tok
    mesh = sh.current_mesh()
    ep = sh.model_axis_size() if mesh is not None else 1

    y_shared = 0.0
    if cfg.n_shared_experts:
        y_shared = L.swiglu(x, p["ws_gate"], p["ws_up"], p["ws_down"])

    seq_shard = (S % ep == 0) and S >= ep
    if impl == "dense" or mesh is None or ep == 1 or E % ep:
        # oracle: compute all experts for all tokens (tiny configs only)
        x2d = x.reshape(-1, D)
        gates, idx = _route(x2d, p["router"], k)
        g = jnp.einsum("td,edf->tef", x2d, p["w_gate"])
        u = jnp.einsum("td,edf->tef", x2d, p["w_up"])
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
        y_all = jnp.einsum("tef,efd->ted", h, p["w_down"])
        sel = jax.nn.one_hot(idx, E, dtype=jnp.float32)   # (T,k,E)
        w = jnp.einsum("tke,tk->te", sel, gates)
        y = jnp.einsum("te,ted->td", w, y_all).astype(x.dtype)
        return y.reshape(B, S, D) + y_shared

    # ---- expert-parallel shard_map path ------------------------------------
    batch_axes = sh.resolve("batch", B, mesh)
    batch_tuple = (batch_axes if isinstance(batch_axes, tuple)
                   else ((batch_axes,) if batch_axes else ()))
    x_spec = P(batch_axes, "model" if seq_shard else None, None)
    fsdp_ax = sh.resolve("fsdp", cfg.d_model, mesh) or "data"
    w_spec = P("model", fsdp_ax, None)                     # (E, D, F)
    wd_spec = P("model", None, fsdp_ax)                    # (E, F, D)
    r_spec = P(None, None)

    dp = sh._axes_size(mesh, batch_tuple) if batch_tuple else 1
    if seq_shard:
        T_loc = (B // dp) * (S // ep)
    else:
        # decode path: tokens replicated over 'model'; each shard takes a
        # slice of ceil(T/ep) tokens, results psum'd back (the EP decode
        # all-reduce)
        T_loc = -(-((B // dp) * S) // ep)
    C = max(8, -(-int(cfg.capacity_factor * T_loc * k) // E))

    def inner(x_loc, router, wg, wu, wd):
        Bl, Sl, _ = x_loc.shape
        x2d_full = x_loc.reshape(-1, D)
        Tfull = x2d_full.shape[0]
        if seq_shard:
            x2d = x2d_full
        else:
            me = jax.lax.axis_index("model")
            c = T_loc
            pad = c * ep - Tfull
            xp = jnp.pad(x2d_full, ((0, pad), (0, 0)))
            x2d = jax.lax.dynamic_slice_in_dim(xp, me * c, c, axis=0)
        T = x2d.shape[0]
        gates, idx = _route(x2d, router, k)
        buf, gate_buf, tok_buf = _dispatch(x2d, gates, idx, E, C)
        # a2a: (E, C, D) -> (E/ep, C*ep, D) on each shard
        buf = _a2a(buf, "model", split=0, concat=1, impl=impl, axis_size=ep)
        # per-expert ZeRO-3 weight gathering (memory-bounded)
        y = _expert_mlp_zero3(buf, wg, wu, wd, fsdp_ax,
                              unroll=cfg.scan_unroll)
        y = _a2a(y, "model", split=1, concat=0, impl=impl, axis_size=ep)
        # combine: scatter-add gated outputs back to token positions
        flat_y = (y * gate_buf[..., None]).astype(x2d.dtype).reshape(E * C, D)
        flat_tok = tok_buf.reshape(E * C)
        out = jnp.zeros_like(x2d).at[
            jnp.where(flat_tok >= 0, flat_tok, T)].add(flat_y, mode="drop")
        if not seq_shard:
            me = jax.lax.axis_index("model")
            c = T_loc
            pad = c * ep - Tfull
            full = jnp.zeros((c * ep, D), x2d.dtype)
            full = jax.lax.dynamic_update_slice_in_dim(full, out, me * c, 0)
            full = jax.lax.psum(full, "model")
            out = full[:Tfull]
        return out.reshape(Bl, Sl, D)

    y = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(x_spec, r_spec, w_spec, w_spec, wd_spec),
        out_specs=x_spec,
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return y + y_shared
