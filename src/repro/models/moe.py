"""Mixture-of-Experts FFN with sort-based expert-parallel dispatch.

Why not GShard one-hot dispatch: the (tokens, E, capacity) dispatch einsum
costs 2*T*E*C*d FLOPs -- at 60 experts / top-4 that *exceeds* the expert
FFN FLOPs themselves and its mask tensor dwarfs VMEM/HBM budgets.  Instead
we use the production pattern (DeepSpeed-MoE / dropless-style):

  1. top-k routing (GSPMD side, tiny).
  2. inside shard_map over (batch axes x model axis):
     a. sort the T_l*k (token, expert) slots by expert id -- destination
        ranks become contiguous;
     b. gather into fixed-capacity per-rank send buffers (mp, C, d);
     c. lax.all_to_all over the model axis (expert parallelism);
     d. locally sort received rows by local expert, gather to (E_l, Ce, d),
        run the gated-FFN einsums (the only "real" FLOPs);
     e. inverse gathers + all_to_all back + weighted scatter-add combine.
  3. load-balance aux loss (GSPMD side).

Everything is fixed-shape (rank capacity C and expert capacity Ce follow
the usual capacity-factor convention; overflow tokens drop, underflow pads
with zero rows).  A `groups` knob scans the tokens in chunks to bound live
buffer memory (and lets XLA overlap the per-group all_to_alls with the
previous group's expert compute).

Expert counts that do not divide the model-axis size are padded with dead
experts (router logits forced to -inf), e.g. qwen2-moe's 60 -> 64.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .layers import act_fn


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int               # routed experts (logical)
    top_k: int
    d_ff_expert: int
    n_shared: int = 0            # shared experts (fused into one gated FFN)
    d_ff_shared: int = 0
    capacity_factor: float = 1.25
    expert_capacity_factor: float = 1.5
    aux_loss_weight: float = 0.01
    groups: int = 1              # token chunks scanned inside shard_map
    pad_multiple: int = 16       # pad n_experts up to a multiple of this

    @property
    def n_experts_padded(self) -> int:
        m = self.pad_multiple
        return -(-self.n_experts // m) * m

    @property
    def d_ff_shared_total(self) -> int:
        return self.d_ff_shared if self.d_ff_shared else 0


def _round8(x: int) -> int:
    return max(8, -(-x // 8) * 8)


# ---------------------------------------------------------------------------
# Routing (GSPMD side)
# ---------------------------------------------------------------------------
def route(x_flat: jnp.ndarray, router_w: jnp.ndarray, cfg: MoEConfig):
    """x (T, d) -> (gates (T, k) f32, eids (T, k) i32, aux_loss scalar)."""
    logits = (x_flat.astype(jnp.float32) @ router_w.astype(jnp.float32))
    e_pad = cfg.n_experts_padded
    if e_pad > cfg.n_experts:  # dead experts: never routable
        neg = jnp.full((logits.shape[0], e_pad - cfg.n_experts), -1e30,
                       jnp.float32)
        logits = jnp.concatenate([logits, neg], axis=-1)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eids = jax.lax.top_k(probs, cfg.top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # load-balance loss (Switch-style): E * sum_e f_e * p_e
    t = logits.shape[0]
    onehot = jax.nn.one_hot(eids[:, 0], e_pad, dtype=jnp.float32)
    f = onehot.mean(0)
    p = probs.mean(0)
    aux = cfg.n_experts * jnp.sum(f * p) * cfg.aux_loss_weight
    return gates, eids.astype(jnp.int32), aux


# ---------------------------------------------------------------------------
# shard_map body
# ---------------------------------------------------------------------------
def _expert_ffn(xg: jnp.ndarray, wg, wi, wo, activation: str) -> jnp.ndarray:
    """(E_l, Ce, d) x (E_l, d, f) -> (E_l, Ce, d) gated FFN."""
    g = act_fn(activation)(jnp.einsum("ecd,edf->ecf", xg, wg))
    h = g * jnp.einsum("ecd,edf->ecf", xg, wi)
    return jnp.einsum("ecf,efd->ecd", h, wo)


def _moe_group_local(xt, gates, eids, wg, wi, wo, *, cfg: MoEConfig,
                     model_axis: str, activation: str):
    """One token group on one device.  xt (Tg, d); gates/eids (Tg, k).

    Runs steps 2a-2e of the module docstring.  All shapes static.
    """
    tg, d = xt.shape
    k = cfg.top_k
    mp = jax.lax.axis_size(model_axis)
    e_pad = cfg.n_experts_padded
    e_l = e_pad // mp
    n_slot = tg * k
    cap = _round8(int(cfg.capacity_factor * n_slot / mp))
    # expected rows per local expert = (mp ranks x n_slot) / e_pad; sizing
    # by the worst-case mp*cap instead multiplies expert FLOPs and buffers
    # by ~mp (measured 13-20x useless compute on qwen/moonshot)
    cap_e = _round8(int(cfg.expert_capacity_factor * mp * n_slot / e_pad))

    flat_e = eids.reshape(-1)                      # (n_slot,)
    flat_g = gates.reshape(-1)
    flat_t = jnp.arange(n_slot, dtype=jnp.int32) // k

    # --- 2a: sort slots by expert id (ranks contiguous) --------------------
    perm = jnp.argsort(flat_e)
    s_e = flat_e[perm]
    s_t = flat_t[perm]
    rank_of = s_e // e_l                           # (n_slot,) sorted too
    seg_start = jnp.searchsorted(rank_of, jnp.arange(mp, dtype=jnp.int32),
                                 side="left").astype(jnp.int32)
    seg_end = jnp.searchsorted(rank_of, jnp.arange(mp, dtype=jnp.int32),
                               side="right").astype(jnp.int32)

    # --- 2b: fixed-capacity send buffers ------------------------------------
    idx = seg_start[:, None] + jnp.arange(cap, dtype=jnp.int32)[None, :]
    valid = idx < seg_end[:, None]                 # (mp, cap)
    idx_c = jnp.clip(idx, 0, n_slot - 1)
    send_tok = jnp.where(valid, s_t[idx_c], 0)
    send_eid = jnp.where(valid, s_e[idx_c] % e_l, -1)       # local expert id
    send_x = jnp.where(valid[..., None], xt[send_tok], 0.0)  # (mp, cap, d)

    # --- 2c: expert-parallel exchange ---------------------------------------
    recv_x = jax.lax.all_to_all(send_x, model_axis, 0, 0, tiled=True)
    recv_eid = jax.lax.all_to_all(send_eid, model_axis, 0, 0, tiled=True)
    rx = recv_x.reshape(mp * cap, d)
    re = recv_eid.reshape(mp * cap)                # -1 = padding

    # --- 2d: local per-expert gather + FFN ----------------------------------
    sort_key = jnp.where(re < 0, e_l, re)          # invalid rows sort last
    perm2 = jnp.argsort(sort_key)
    r_e = sort_key[perm2]
    estart = jnp.searchsorted(r_e, jnp.arange(e_l, dtype=jnp.int32),
                              side="left").astype(jnp.int32)
    eend = jnp.searchsorted(r_e, jnp.arange(e_l, dtype=jnp.int32),
                            side="right").astype(jnp.int32)
    eidx = estart[:, None] + jnp.arange(cap_e, dtype=jnp.int32)[None, :]
    evalid = eidx < eend[:, None]                  # (e_l, cap_e)
    eidx_c = jnp.clip(eidx, 0, mp * cap - 1)
    rows = jnp.where(evalid, perm2[eidx_c], 0)
    xg = jnp.where(evalid[..., None], rx[rows], 0.0)        # (e_l, cap_e, d)
    yg = _expert_ffn(xg.astype(wg.dtype), wg, wi, wo, activation)

    # --- 2e: inverse path ----------------------------------------------------
    # scatter expert outputs back to recv-row order
    y_rx = jnp.zeros((mp * cap, d), yg.dtype)
    y_rx = y_rx.at[rows.reshape(-1)].add(
        jnp.where(evalid[..., None], yg, 0.0).reshape(-1, d))
    y_send = jax.lax.all_to_all(y_rx.reshape(mp, cap, d), model_axis, 0, 0,
                                tiled=True)        # back to sender layout
    # combine: slot j's result sits at (rank_of[j], j - seg_start[rank_of[j]])
    pos = jnp.arange(n_slot, dtype=jnp.int32) - seg_start[rank_of]
    ok = pos < cap                                  # dropped slots contribute 0
    row_flat = jnp.clip(rank_of * cap + pos, 0, mp * cap - 1)
    slot_y = jnp.where(ok[:, None], y_send.reshape(mp * cap, d)[row_flat], 0.0)
    w = flat_g[perm][:, None].astype(slot_y.dtype)
    out = jnp.zeros((tg, d), slot_y.dtype)
    out = out.at[s_t].add(slot_y * w)
    return out


def _moe_local(xt, gates, eids, wg, wi, wo, *, cfg: MoEConfig,
               model_axis: str, activation: str):
    """All local tokens, scanned in `groups` chunks.

    Tokens arrive replicated along the model axis (they are sharded over
    the batch axes only).  Each model rank therefore takes its own 1/mp
    slice and the slices' outputs merge with one psum -- without this every
    expert would process mp duplicate copies of its tokens (measured 16x
    FLOPs waste).  Tiny token counts (decode) fall back to the replicated
    path (duplicated but correct).

    The group count adapts downward to the largest divisor of the local
    token count."""
    mp = jax.lax.axis_size(model_axis)
    t_full, d = xt.shape
    sliced = t_full % mp == 0 and t_full >= mp and (t_full // mp) >= 1
    if sliced:
        sl = t_full // mp
        idx = jax.lax.axis_index(model_axis)
        xt = jax.lax.dynamic_slice_in_dim(xt, idx * sl, sl, 0)
        gates = jax.lax.dynamic_slice_in_dim(gates, idx * sl, sl, 0)
        eids = jax.lax.dynamic_slice_in_dim(eids, idx * sl, sl, 0)
    t_l = xt.shape[0]
    g = max(gg for gg in range(1, min(cfg.groups, t_l) + 1) if t_l % gg == 0)
    fn = functools.partial(_moe_group_local, cfg=cfg, model_axis=model_axis,
                           activation=activation)
    if g == 1:
        out = fn(xt, gates, eids, wg, wi, wo)
    else:
        # remat each group: the inner scan otherwise saves every group's
        # dispatch/expert buffers for the backward pass (measured: 60 GiB
        # on qwen2-moe train_4k vs ~9 GiB with per-group recompute)
        fn = jax.checkpoint(fn)

        def body(_, inp):
            xg, gg, eg = inp
            return None, fn(xg, gg, eg, wg, wi, wo)

        _, outs = jax.lax.scan(
            body, None,
            (xt.reshape(g, t_l // g, d),
             gates.reshape(g, t_l // g, -1),
             eids.reshape(g, t_l // g, -1)))
        out = outs.reshape(t_l, d)
    if sliced:
        full = jnp.zeros((t_full, d), out.dtype)
        full = jax.lax.dynamic_update_slice_in_dim(full, out, idx * sl, 0)
        return jax.lax.psum(full, model_axis)
    return out


# ---------------------------------------------------------------------------
# Public layer
# ---------------------------------------------------------------------------
def moe_ffn(x: jnp.ndarray, params: dict, cfg: MoEConfig, *,
            mesh: Optional[Mesh], batch_axes: tuple, model_axis: Optional[str],
            activation: str = "silu"):
    """MoE FFN block.  x (B, S, d) sharded over batch_axes.

    params: router (d, E), we_gate/we_in (E_pad, d, fe), we_out (E_pad, fe, d)
            [+ ws_gate/ws_in/ws_out for the fused shared expert].
    Returns (out (B, S, d), aux_loss).
    """
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    gates, eids, aux = route(xt, params["router"], cfg)

    if mesh is None or model_axis is None or model_axis not in mesh.axis_names:
        # single-axis fallback: pure local compute (tests / CPU smoke)
        out = _moe_local_nosharding(xt, gates, eids, params["we_gate"],
                                    params["we_in"], params["we_out"],
                                    cfg=cfg, activation=activation)
    else:
        from jax.experimental.shard_map import shard_map
        # batch axes only when the flat token count divides them (decode
        # cells can have 1 token per sequence, batch 1)
        t = b * s
        ndp = 1
        ba = batch_axes if batch_axes else None
        if ba is not None:
            for a in (ba if isinstance(ba, tuple) else (ba,)):
                ndp *= mesh.devices.shape[mesh.axis_names.index(a)]
            if t < ndp or t % ndp != 0:
                ba = None
        tok_spec = P(ba, None)
        w_spec = P(model_axis, None, None)
        out = shard_map(
            functools.partial(_moe_local, cfg=cfg, model_axis=model_axis,
                              activation=activation),
            mesh=mesh,
            in_specs=(tok_spec, tok_spec, tok_spec, w_spec, w_spec, w_spec),
            out_specs=tok_spec,
            check_rep=False,
        )(xt, gates.astype(x.dtype), eids, params["we_gate"],
          params["we_in"], params["we_out"])

    if cfg.n_shared:
        from .layers import gated_mlp
        shared = gated_mlp(xt, params["ws_gate"], params["ws_in"],
                           params["ws_out"], activation)
        out = out + shared
    return out.reshape(b, s, d).astype(x.dtype), aux


def _moe_local_nosharding(xt, gates, eids, wg, wi, wo, *, cfg: MoEConfig,
                          activation: str):
    """Single-device reference path (mp=1): same sort/gather code with a
    trivial 'exchange' -- also the oracle for the shard_map path."""
    t, d = xt.shape
    k = cfg.top_k
    e_pad = cfg.n_experts_padded
    n_slot = t * k
    cap_e = _round8(int(cfg.expert_capacity_factor * n_slot / e_pad))
    flat_e = eids.reshape(-1)
    flat_g = gates.reshape(-1)
    flat_t = jnp.arange(n_slot, dtype=jnp.int32) // k
    perm = jnp.argsort(flat_e)
    s_e = flat_e[perm]
    s_t = flat_t[perm]
    estart = jnp.searchsorted(s_e, jnp.arange(e_pad, dtype=jnp.int32),
                              side="left").astype(jnp.int32)
    eend = jnp.searchsorted(s_e, jnp.arange(e_pad, dtype=jnp.int32),
                            side="right").astype(jnp.int32)
    eidx = estart[:, None] + jnp.arange(cap_e, dtype=jnp.int32)[None, :]
    evalid = eidx < eend[:, None]
    eidx_c = jnp.clip(eidx, 0, n_slot - 1)
    rows = jnp.where(evalid, s_t[eidx_c], 0)
    xg = jnp.where(evalid[..., None], xt[rows], 0.0)
    yg = _expert_ffn(xg.astype(wg.dtype), wg, wi, wo, activation)
    # combine: slot j -> (expert e = s_e[j], c = j - estart[e])
    pos = jnp.arange(n_slot, dtype=jnp.int32) - estart[s_e]
    ok = pos < cap_e
    flat_idx = jnp.clip(s_e * cap_e + pos, 0, e_pad * cap_e - 1)
    slot_y = jnp.where(ok[:, None], yg.reshape(-1, d)[flat_idx], 0.0)
    w = flat_g[perm][:, None].astype(slot_y.dtype)
    out = jnp.zeros((t, d), slot_y.dtype)
    return out.at[s_t].add(slot_y * w)
