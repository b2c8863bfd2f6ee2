"""Mixture-of-Experts layer: top-k routing, DP-grouped capacity dispatch.

Dispatch strategy (designed for the (data, model) mesh, see DESIGN.md §4):

  * tokens are reshaped to [dp_groups, T, d] so that each data-parallel group
    dispatches *its own* tokens — no cross-data-axis scatter traffic; the
    only expert-parallel communication is the gather into / out of the
    ``model``-sharded expert buffers (the EP all-to-all).
  * slot assignment is computed with a cumsum over a [g, T*k, E] one-hot
    (no O(T*E*C) dispatch tensor); tokens beyond expert capacity are dropped
    (GShard semantics, capacity_factor configurable).
  * the expert FFN is a single grouped einsum over the expert-sharded weight
    stack — local matmuls on every device.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.config import ArchConfig
from repro.parallel.sharding import Ax, ParamDecl, ShardingCtx
from repro.models.layers import mlp, mlp_decls


def moe_decls(arch: ArchConfig) -> dict:
    d = arch.d_model
    m = arch.moe
    fe = m.d_ff_expert
    decls = dict(
        router=ParamDecl((d, m.n_experts), (Ax.EMBED, None), scale=0.02),
        we_gate=ParamDecl((m.n_experts, d, fe), (Ax.EXPERT, Ax.EMBED, None)),
        we_up=ParamDecl((m.n_experts, d, fe), (Ax.EXPERT, Ax.EMBED, None)),
        we_down=ParamDecl((m.n_experts, fe, d), (Ax.EXPERT, None, Ax.EMBED)),
    )
    if m.n_shared_experts:
        decls["shared"] = mlp_decls(d, fe * m.n_shared_experts)
    return decls


def _capacity(tokens_per_group: int, top_k: int, n_experts: int, cf: float) -> int:
    c = int(tokens_per_group * top_k / n_experts * cf)
    return max(4, c)


def moe_ffn(x, p, arch: ArchConfig, ctx: ShardingCtx, *, positions=None):
    """x: [b, s, d] (batch over data axes). Returns [b, s, d] + aux loss."""
    b, s, d = x.shape
    m = arch.moe
    E, K = m.n_experts, m.top_k
    dp = ctx.dp_size
    assert b % dp == 0, (b, dp)
    T = (b // dp) * s
    C = _capacity(T, K, E, m.capacity_factor)

    xg = x.reshape(dp, T, d)
    xg = ctx.constrain(xg, Ax.DP_GROUP, None, None)

    # --- routing (fp32) ------------------------------------------------------
    logits = (xg.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                  # [g, T, E]
    gates, eidx = jax.lax.top_k(probs, K)                    # [g, T, K]
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)

    # load-balancing auxiliary loss (Switch-style)
    me = jnp.mean(probs, axis=(0, 1))                        # [E]
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(eidx, E, dtype=jnp.float32), axis=2),
        axis=(0, 1)) / K                                     # assignments/tok
    aux = E * jnp.sum(me * ce)                               # ==1 if balanced

    # --- slot assignment ------------------------------------------------------
    eflat = eidx.reshape(dp, T * K)                          # [g, TK]
    oh = jax.nn.one_hot(eflat, E, dtype=jnp.int32)           # [g, TK, E]
    pos_all = jnp.cumsum(oh, axis=1) - 1                     # position per expert
    pos = jnp.take_along_axis(pos_all, eflat[..., None], axis=-1)[..., 0]
    keep = pos < C                                           # dropped beyond capacity

    # slot -> token map: slot_tok[g, e, c] = token index (or T: dummy)
    tok_of_entry = jnp.arange(T * K) // K                    # [TK]
    gi = jnp.broadcast_to(jnp.arange(dp)[:, None], (dp, T * K))
    e_safe = jnp.where(keep, eflat, 0)
    pos_safe = jnp.where(keep, pos, C)                       # C -> dropped row
    slot_tok = jnp.full((dp, E, C + 1), T, jnp.int32)
    slot_tok = slot_tok.at[gi, e_safe, pos_safe].set(
        jnp.where(keep, tok_of_entry[None], T), mode="drop")
    slot_tok = slot_tok[:, :, :C]                            # [g, E, C]

    # --- dispatch gather ------------------------------------------------------
    xg_pad = jnp.concatenate([xg, jnp.zeros((dp, 1, d), xg.dtype)], axis=1)
    xe = jax.vmap(lambda xp, st: xp[st])(xg_pad, slot_tok.reshape(dp, E * C))
    xe = xe.reshape(dp, E, C, d)
    xe = ctx.constrain(xe, Ax.DP_GROUP, Ax.EXPERT_ACT, None, None)

    # --- expert FFN (local matmuls: dp over data, E over model) ---------------
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", xe, ctx.cast(p["we_gate"]))) \
        * jnp.einsum("gecd,edf->gecf", xe, ctx.cast(p["we_up"]))
    ye = jnp.einsum("gecf,efd->gecd", h, ctx.cast(p["we_down"]))
    ye = ctx.constrain(ye, Ax.DP_GROUP, Ax.EXPERT_ACT, None, None)

    # --- combine gather -------------------------------------------------------
    flat_slot = (e_safe * C + jnp.minimum(pos_safe, C - 1))  # [g, TK]
    yflat = jax.vmap(lambda ya, fs: ya[fs])(ye.reshape(dp, E * C, d), flat_slot)
    yflat = yflat * (keep[..., None] * gates.reshape(dp, T * K)[..., None]
                     ).astype(yflat.dtype)
    y = jnp.sum(yflat.reshape(dp, T, K, d), axis=2)
    y = y.reshape(b, s, d)

    if m.n_shared_experts:
        y = y + mlp(x, p["shared"], ctx)
    return y, aux


# ---------------------------------------------------------------------------
# Optimized expert parallelism (shard_map) — the §Perf hillclimb result
# ---------------------------------------------------------------------------


def moe_ffn_ep(x, p, arch: ArchConfig, ctx: ShardingCtx, *, positions=None):
    """Expert-parallel MoE with *explicit* per-rank dispatch.

    The GSPMD auto-sharded path (``moe_ffn``) lowers the data-dependent
    dispatch/combine gathers into full all-gathers of the [E, C, d] expert
    buffers across the model axis — measured 719 GB/device collective bytes
    on moonshot/train_4k (EXPERIMENTS.md §Perf). This version makes the
    communication explicit with shard_map:

      * activations enter replicated over ``model`` (the Megatron-SP
        all-gather that already exists at the block boundary);
      * every model rank routes all local tokens but *dispatches only to
        its own E/ep experts* — gather, expert FFN, and scatter-combine are
        entirely local;
      * partial outputs are summed with one psum over ``model``
        (2 x activation bytes, vs C-factor-larger buffer all-gathers).

    Numerically identical to ``moe_ffn`` up to summation order (tested in
    tests/test_moe_ep.py on an 8-device mesh).
    """
    from jax.sharding import PartitionSpec as P

    if ctx.mesh is None:
        return moe_ffn(x, p, arch, ctx, positions=positions)

    m = arch.moe
    E, K = m.n_experts, m.top_k
    ep = ctx.model_size
    assert E % ep == 0, (E, ep)
    e_loc = E // ep
    b, s, d = x.shape
    data_axes = tuple(ctx.mesh_cfg.data_axes)
    dp = ctx.dp_size
    T = (b // dp) * s
    C = _capacity(T, K, E, m.capacity_factor)

    def block(xb, router, wg, wu, wd):
        # xb: [b_loc, s, d] (replicated over model); w*: [e_loc, ...]
        rank = jax.lax.axis_index("model")
        tb, sb, _ = xb.shape
        xt = xb.reshape(tb * sb, d)
        logits = xt.astype(jnp.float32) @ router.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, eidx = jax.lax.top_k(probs, K)
        gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)

        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jnp.sum(jax.nn.one_hot(eidx, E, dtype=jnp.float32),
                              axis=1), axis=0) / K
        aux = E * jnp.sum(me * ce)
        # replicate across every mesh axis (tokens differ per data rank)
        aux = jax.lax.pmean(aux, tuple(ctx.mesh.axis_names))

        # global slot positions (every rank computes identically)
        eflat = eidx.reshape(-1)                          # [T*K]
        oh = jax.nn.one_hot(eflat, E, dtype=jnp.int32)
        pos = jnp.take_along_axis(jnp.cumsum(oh, 0) - 1,
                                  eflat[:, None], 1)[:, 0]
        keep = pos < C

        # restrict to this rank's experts
        lo = rank * e_loc
        own = (eflat >= lo) & (eflat < lo + e_loc) & keep
        e_rel = jnp.where(own, eflat - lo, 0)
        pos_s = jnp.where(own, pos, C)
        tok = jnp.arange(eflat.shape[0]) // K

        slot_tok = jnp.full((e_loc, C + 1), tb * sb, jnp.int32)
        slot_tok = slot_tok.at[e_rel, pos_s].set(
            jnp.where(own, tok, tb * sb), mode="drop")[:, :C]

        xt_pad = jnp.concatenate(
            [xt, jnp.zeros((1, d), xt.dtype)], axis=0)
        xe = xt_pad[slot_tok.reshape(-1)].reshape(e_loc, C, d)

        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg.astype(xe.dtype))) \
            * jnp.einsum("ecd,edf->ecf", xe, wu.astype(xe.dtype))
        ye = jnp.einsum("ecf,efd->ecd", h, wd.astype(xe.dtype))

        # local combine: scatter-add each slot back to its token
        flat_tok = slot_tok.reshape(-1)
        gate_of_entry = gates.reshape(-1)
        # gate per slot: invert via the entry -> slot map
        entry_slot_gate = jnp.where(own, gate_of_entry, 0.0)
        gate_slot = jnp.zeros((e_loc, C + 1), jnp.float32).at[
            e_rel, pos_s].set(entry_slot_gate, mode="drop")[:, :C]
        contrib = ye * gate_slot[..., None].astype(ye.dtype)
        y = jnp.zeros((tb * sb + 1, d), ye.dtype).at[flat_tok].add(
            contrib.reshape(-1, d), mode="drop")[:-1]
        y = jax.lax.psum(y, "model")
        return y.reshape(tb, sb, d), aux

    xspec = P(data_axes, None, None)
    wspec = P("model", None, None)
    y, aux = jax.shard_map(
        block, mesh=ctx.mesh,
        in_specs=(xspec, P(None, None), wspec, wspec, wspec),
        out_specs=(xspec, P()),
        check_vma=False,
    )(x, p["router"], p["we_gate"], p["we_up"], p["we_down"])

    if m.n_shared_experts:
        y = y + mlp(x, p["shared"], ctx)
    return y, aux
