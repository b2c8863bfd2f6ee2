"""Pallas kernel: synapse-array event path.

i[n, b, c] = sum_r ev[n, b, r] * w[n, r, c] * (addr_store[n, r, c] ==
addr_event[n, b, r])

Hardware adaptation (DESIGN.md): on BSS-2 the address comparison happens in
each synapse circuit as the event ripples down the row. On TPU the natural
mapping is a *masked* block matmul: the weight/address tile lives in VMEM,
the per-(batch,row) event address broadcasts against the stored-address
tile, and the masked tile contracts against the event vector. Tiles are
VPU aligned (row x 128-lane column blocks); the reduction runs over the
row-block grid axis with an accumulator in the output block.

Mosaic layout: each batch row's events and addresses are turned into a
[rb, 1] column by a 2-D transpose of a [1, rb] row, and the masked tile
is formed per batch row as [rb, cb] — no 3-D int8 shape cast, which the
TPU compiler refuses. Row blocks are the whole R or a multiple of 128
(the lane rule for the [bb, rb] event block).

The leading ``n`` is the **instance grid axis**: a fleet of independent
chip instances (each with its own weights/addresses/events) runs as ONE
kernel launch with instances as the outermost grid dimension — no nested
``jax.vmap`` fold (see ``repro.kernels`` docstring). 2-D operands are
accepted and treated as a single instance.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tile


def _kernel(ev_ref, ea_ref, w_ref, st_ref, out_ref):
    r_idx = pl.program_id(3)

    @pl.when(r_idx == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ev = ev_ref[0].astype(jnp.float32)              # [bb, rb]
    ea = ea_ref[0]                                  # [bb, rb] int32
    w = w_ref[0].astype(jnp.float32)                # [rb, cb]
    st = st_ref[0].astype(jnp.int32)                # [rb, cb]

    rows = []
    for b in range(ev.shape[0]):                    # static unroll over bb
        ev_col = jnp.transpose(ev[b:b + 1, :])      # [rb, 1]
        ea_col = jnp.transpose(ea[b:b + 1, :])
        mask = (st == ea_col).astype(jnp.float32)   # [rb, cb]
        rows.append(jnp.sum(ev_col * (w * mask), axis=0, keepdims=True))
    out_ref[0] += jnp.concatenate(rows, axis=0)


@functools.partial(jax.jit, static_argnames=("bb", "cb", "rb", "interpret"))
def synaptic_current_pallas(events, event_addr, weights, addresses, *,
                            bb: int = 8, cb: int = 128, rb: int = 128,
                            interpret: bool = False):
    """events: [N, B, R] f32; event_addr: [N, B, R] int; weights/addresses:
    [N, R, C] i8. Returns [N, B, C] f32. 2-D operands (no instance axis)
    are promoted to N=1 and squeezed back."""
    squeeze = events.ndim == 2
    if squeeze:
        events, event_addr = events[None], event_addr[None]
        weights, addresses = weights[None], addresses[None]
    N, B, R = events.shape
    C = weights.shape[-1]
    bb = min(bb, B)
    cb = tile(cb, C)
    rb = tile(rb, R)
    assert B % bb == 0, (B, bb)
    event_addr = event_addr.astype(jnp.int32)
    grid = (N, B // bb, C // cb, R // rb)
    ev_spec = pl.BlockSpec((1, bb, rb), lambda n, i, j, k: (n, i, k))
    w_spec = pl.BlockSpec((1, rb, cb), lambda n, i, j, k: (n, k, j))
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[ev_spec, ev_spec, w_spec, w_spec],
        out_specs=pl.BlockSpec((1, bb, cb), lambda n, i, j, k: (n, i, j)),
        out_shape=jax.ShapeDtypeStruct((N, B, C), jnp.float32),
        interpret=interpret,
    )(events, event_addr, weights, addresses)
    return out[0] if squeeze else out
