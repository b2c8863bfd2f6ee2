"""Pallas kernel: the PPU vector-unit inner loop, row-parallel.

Fuses, per synapse tile:
  1. CADC digitization of the causal/anti-causal capacitor voltages
     (8-bit, per-column offset/gain),
  2. eligibility e = (q_causal - q_acausal)/255,
  3. R-STDP weight update dw = eta * mod[c] * e + xi,
  4. saturating 6-bit write-back.

This mirrors the silicon dataflow exactly: the hardware PPU reads one
synapse row + one CADC row per vector op, computes in fixed point across
the column lanes, and writes the row back through the full-custom SRAM
controller. Lanes == the 128-wide column blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(w_ref, ac_ref, aa_ref, off_ref, gain_ref, mod_ref, xi_ref,
            wout_ref, elig_ref, *, eta: float, cadc_scale: float,
            wmax: int, cadc_max: int):
    w = w_ref[...].astype(jnp.float32)            # [rb, cb]
    ac = ac_ref[...].astype(jnp.float32)
    aa = aa_ref[...].astype(jnp.float32)
    off = off_ref[...].astype(jnp.float32)        # [1, cb]
    gain = gain_ref[...].astype(jnp.float32)
    mod = mod_ref[...].astype(jnp.float32)        # [1, cb]
    xi = xi_ref[...].astype(jnp.float32)

    def digitize(a):
        code = a * (gain * cadc_scale) + off
        return jnp.clip(jnp.round(code), 0.0, float(cadc_max))

    qc = digitize(ac)
    qa = digitize(aa)
    elig = (qc - qa) / float(cadc_max)
    w_new = w + eta * mod * elig + xi
    wout_ref[...] = jnp.clip(jnp.round(w_new), 0.0, float(wmax)
                             ).astype(jnp.int8)
    elig_ref[...] = elig


@functools.partial(jax.jit, static_argnames=("eta", "cadc_scale", "wmax",
                                             "cadc_max", "rb", "cb",
                                             "interpret"))
def rstdp_update_pallas(weights, a_causal, a_acausal, cadc_offset, cadc_gain,
                        mod, xi, *, eta: float, cadc_scale: float = 8.0,
                        wmax: int = 63, cadc_max: int = 255,
                        rb: int = 64, cb: int = 128,
                        interpret: bool = False):
    """weights [R, C] i8; a_* [R, C] f32; cadc_offset/gain, mod [C] f32;
    xi [R, C] f32. Returns (new_weights i8, eligibility f32)."""
    R, C = weights.shape
    rb = min(rb, R)
    cb = min(cb, C)
    assert R % rb == 0 and C % cb == 0
    grid = (R // rb, C // cb)
    row_spec = pl.BlockSpec((rb, cb), lambda i, j: (i, j))
    col_spec = pl.BlockSpec((1, cb), lambda i, j: (0, j))
    out = pl.pallas_call(
        functools.partial(_kernel, eta=eta, cadc_scale=cadc_scale,
                          wmax=wmax, cadc_max=cadc_max),
        name="ppu_update",
        grid=grid,
        in_specs=[row_spec, row_spec, row_spec, col_spec, col_spec, col_spec,
                  row_spec],
        out_specs=[row_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct((R, C), jnp.int8),
                   jax.ShapeDtypeStruct((R, C), jnp.float32)],
        interpret=interpret,
    )(weights, a_causal, a_acausal, cadc_offset[None], cadc_gain[None],
      mod[None], xi)
    return out[0], out[1]
