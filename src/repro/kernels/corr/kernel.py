"""Pallas kernel: fused T-step correlation-sensor window.

Integrates T timesteps of the causal/anti-causal accumulation for one
synapse tile without leaving VMEM:

    tp[t] = lam * tp[t-1] + pre[t]         (presynaptic trace, per row)
    tq[t] = lam * tq[t-1] + post[t]        (postsynaptic trace, per col)
    a_c  += tp[t] (outer) post[t]          (saturating)
    a_a  += pre[t] (outer) tq[t]           (saturating)

Hardware adaptation (DESIGN.md): the analog sensor does this "for free" on
capacitors; the naive digital port re-reads the [R, C] accumulators from
HBM every step. The TPU-native version tiles [R, C] into VMEM blocks and
replays the whole T-window per tile — T x fewer HBM round trips; the spike
vectors ([T, rb] + [T, cb]) are tiny. The in-kernel loop preserves per-step
saturation semantics exactly (a post-hoc matmul over time would not).

Mosaic layout: the loop reads step ``t``'s spike rows from the refs
(``pre_ref[0, pl.ds(t, 1), :]``: a dynamic index into a loaded value
lowers to ``dynamic_slice``, which the TPU compiler refuses), traces are
[1, n] rows, and the row-side factors of the two outer products become
[rb, 1] columns by a 2-D transpose. Row blocks are the whole R or a
multiple of 128 (the lane rule for the [T, rb] spike block).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tile


def _kernel(pre_ref, post_ref, tp0_ref, tq0_ref, ac0_ref, aa0_ref,
            ac_ref, aa_ref, tp_ref, tq_ref, *, lam: float, sat: float):
    T = pre_ref.shape[1]

    def body(t, carry):
        tp, tq, ac, aa = carry                  # tp [1, rb], tq [1, cb]
        # step rows are read from the refs (a dynamic sublane load), and
        # the row-side vectors become [rb, 1] columns by a 2-D transpose
        p_t = pre_ref[0, pl.ds(t, 1), :].astype(jnp.float32)
        q_t = post_ref[0, pl.ds(t, 1), :].astype(jnp.float32)
        tp = tp * lam + p_t
        tq = tq * lam + q_t
        ac = jnp.minimum(ac + jnp.transpose(tp) * q_t, sat)
        aa = jnp.minimum(aa + jnp.transpose(p_t) * tq, sat)
        return tp, tq, ac, aa

    tp0 = tp0_ref[0].astype(jnp.float32)
    tq0 = tq0_ref[0].astype(jnp.float32)
    ac0 = ac0_ref[0].astype(jnp.float32)
    aa0 = aa0_ref[0].astype(jnp.float32)
    tp, tq, ac, aa = jax.lax.fori_loop(0, T, body, (tp0, tq0, ac0, aa0))
    ac_ref[0] = ac
    aa_ref[0] = aa
    tp_ref[0] = tp
    tq_ref[0] = tq


@functools.partial(jax.jit,
                   static_argnames=("lam", "sat", "rb", "cb", "interpret"))
def correlation_window_pallas(pre, post, tp0, tq0, ac0, aa0, *,
                              lam: float, sat: float = 1023.0,
                              rb: int = 128, cb: int = 128,
                              interpret: bool = False):
    """pre: [N, T, R]; post: [N, T, C]; tp0 [N, R]; tq0 [N, C]; ac0/aa0
    [N, R, C] — the leading N is the instance grid axis (see
    ``repro.kernels``); operands without it are promoted to N=1.

    Returns (a_causal, a_acausal, tp_final, tq_final).
    """
    squeeze = pre.ndim == 2
    if squeeze:
        pre, post, tp0, tq0 = pre[None], post[None], tp0[None], tq0[None]
        ac0, aa0 = ac0[None], aa0[None]
    N, T, R = pre.shape
    C = post.shape[-1]
    rb = tile(rb, R)
    cb = tile(cb, C)
    grid = (N, R // rb, C // cb)
    acc_spec = pl.BlockSpec((1, rb, cb), lambda n, i, j: (n, i, j))
    row_spec = pl.BlockSpec((1, 1, rb), lambda n, i, j: (n, 0, i))
    col_spec = pl.BlockSpec((1, 1, cb), lambda n, i, j: (n, 0, j))
    out = pl.pallas_call(
        functools.partial(_kernel, lam=lam, sat=sat),
        name="corr",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, T, rb), lambda n, i, j: (n, 0, i)),
            pl.BlockSpec((1, T, cb), lambda n, i, j: (n, 0, j)),
            row_spec, col_spec, acc_spec, acc_spec,
        ],
        out_specs=[acc_spec, acc_spec, row_spec, col_spec],
        out_shape=[
            jax.ShapeDtypeStruct((N, R, C), jnp.float32),
            jax.ShapeDtypeStruct((N, R, C), jnp.float32),
            jax.ShapeDtypeStruct((N, 1, R), jnp.float32),
            jax.ShapeDtypeStruct((N, 1, C), jnp.float32),
        ],
        interpret=interpret,
    )(pre, post, tp0[:, None], tq0[:, None], ac0, aa0)
    ac, aa, tp, tq = out
    if squeeze:
        return ac[0], aa[0], tp[0, 0], tq[0, 0]
    return ac, aa, tp[:, 0], tq[:, 0]
