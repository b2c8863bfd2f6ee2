"""Pallas kernel: time-blocked VMEM-resident AdEx neuron scan.

The fused emulation backend leaves ONE per-dt ``lax.scan`` in the trial:
the neuron-state update, an O(C) body paying XLA while-loop overhead per
dt. The AdEx array itself integrates a whole time window on-chip without
round-trips (Aamir et al., arXiv:1804.01906); this kernel is the TPU
analogue — one grid step integrates a whole **time block**:

  * neuron state (v, w, adaptation current, refractory counters, synaptic
    current states, rate counters) lives in a VMEM scratch buffer that
    persists across the (sequential, innermost) time-block grid axis — it
    is read from HBM once per trial and written back once;
  * the pre-fused per-dt synaptic currents stream in as [block, cb]
    slabs, spikes (and optional voltage records) stream out per block;
  * a leading **instance grid axis** maps a fleet of independent chip
    instances onto the grid — one kernel launch per trial, no vmap fold
    (``repro.parallel.sharding.Ax.INSTANCE`` shards the same axis over
    the mesh's data dims).

The per-step math is ``repro.core.adex.integrate_currents`` +
``membrane_step`` — the same op trees as the oracle scan, called per
unrolled step inside the kernel, so the executors cannot fork
semantically (cf. how the PPU-VM executors share ``make_branches``).

State/param packing (rows of the [*, cb] tiles):
  state  [N, 6, C]: v, w, i_exc, i_inh, refrac, rate_counters
  params [N, 12, C]: e_leak, v_thres, delta_t, g_leak, a, b, e_reset,
                     tau_refrac, de, di, alpha, aw
A trailing partial block (T not a multiple of the block size) is handled
in-kernel: padded steps are masked out of the state update and emit no
spikes, so any T is exact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import adex

PARAM_ROWS = ("e_leak", "v_thres", "delta_t", "g_leak", "a", "b",
              "e_reset", "tau_refrac")
DECAY_ROWS = ("de", "di", "alpha", "aw")


def _kernel(ie_ref, ii_ref, st_ref, par_ref, spk_ref, stout_ref, *rest,
            dt: float, use_adex: bool, T: int, blk: int, record_v: bool):
    vrec_ref = rest[0] if record_v else None
    scr = rest[-1]
    b_idx = pl.program_id(2)
    nblk = pl.num_programs(2)

    @pl.when(b_idx == 0)
    def _init():
        scr[...] = st_ref[0]

    par = par_ref[0]                                    # [12, cb]
    params = {k: par[i] for i, k in enumerate(PARAM_ROWS)}
    decays = {k: par[len(PARAM_ROWS) + i] for i, k in enumerate(DECAY_ROWS)}

    v, w, i_exc, i_inh, refrac, rc = (scr[i] for i in range(6))
    padded = T % blk != 0                               # static
    for t in range(blk):                                # static unroll
        i_exc2, i_inh2 = adex.integrate_currents(
            i_exc, i_inh, ie_ref[0, t], ii_ref[0, t], decays)
        v2, w2, refrac2, out = adex.membrane_step(
            v, w, refrac, i_exc2 - i_inh2, params, dt, adex=use_adex,
            decays=decays)
        if padded:                                      # mask tail steps
            valid = (b_idx * blk + t) < T
            v = jnp.where(valid, v2, v)
            w = jnp.where(valid, w2, w)
            refrac = jnp.where(valid, refrac2, refrac)
            i_exc = jnp.where(valid, i_exc2, i_exc)
            i_inh = jnp.where(valid, i_inh2, i_inh)
            out = jnp.where(valid, out, 0.0)
        else:
            v, w, refrac, i_exc, i_inh = v2, w2, refrac2, i_exc2, i_inh2
        rc = rc + out
        spk_ref[0, t] = out
        if record_v:
            vrec_ref[0, t] = v

    scr[...] = jnp.stack([v, w, i_exc, i_inh, refrac, rc])

    @pl.when(b_idx == nblk - 1)
    def _flush():
        stout_ref[0] = scr[...]


@functools.partial(jax.jit, static_argnames=("dt", "use_adex", "T", "blk",
                                             "cb", "record_v", "interpret"))
def neuron_window_pallas(ie_t, ii_t, state6, params12, *, dt: float,
                         use_adex: bool, T: int, blk: int = 32,
                         cb: int = 128, record_v: bool = False,
                         interpret: bool = False):
    """ie_t/ii_t: [N, T_pad, C] f32 (T_pad = ceil(T/blk)*blk, zero-padded);
    state6: [N, 6, C] f32; params12: [N, 12, C] f32.

    Returns (spikes [N, T_pad, C], state_out [N, 6, C][, v_rec]) — the
    caller slices records back to [.., :T].
    """
    N, T_pad, C = ie_t.shape
    assert T_pad % blk == 0 and T_pad - blk < T <= T_pad, (T, T_pad, blk)
    cb = min(cb, C)
    assert C % cb == 0, (C, cb)
    grid = (N, C // cb, T_pad // blk)

    drive_spec = pl.BlockSpec((1, blk, cb), lambda n, c, b: (n, b, c))
    state_spec = pl.BlockSpec((1, 6, cb), lambda n, c, b: (n, 0, c))
    par_spec = pl.BlockSpec((1, 12, cb), lambda n, c, b: (n, 0, c))
    out_specs = [drive_spec, state_spec]
    out_shape = [jax.ShapeDtypeStruct((N, T_pad, C), jnp.float32),
                 jax.ShapeDtypeStruct((N, 6, C), jnp.float32)]
    if record_v:
        out_specs.append(drive_spec)
        out_shape.append(jax.ShapeDtypeStruct((N, T_pad, C), jnp.float32))
    out = pl.pallas_call(
        functools.partial(_kernel, dt=dt, use_adex=use_adex, T=T, blk=blk,
                          record_v=record_v),
        name="neuron_scan",
        grid=grid,
        in_specs=[drive_spec, drive_spec, state_spec, par_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((6, cb), jnp.float32)],
        interpret=interpret,
    )(ie_t, ii_t, state6, params12)
    return tuple(out)
