"""Pallas tile VM: execute a whole PPU-VM program per VMEM tile.

The silicon PPU runs its plasticity kernel out of on-chip SRAM: the
program loops over synapse rows, and every intermediate lives in the
vector unit's registers — weights stream through, the program does not.
This kernel is the TPU analogue: one grid pass over (row, column) tiles
of the synapse array; per tile, the ENTIRE instruction stream executes
with the register file held on-chip (a [N_REGS, rb, cb] carry that the
compiler keeps in VMEM/vregs), so a P-instruction program costs one HBM
round trip instead of P (the scan interpreter re-reads the operand
planes per lax.switch arm).

The instruction words are a scalar-prefetch operand (SMEM): they are the
*data* driving control flow — `fori_loop` over words, `lax.switch` over
opcodes — exactly like the hardware fetches its kernel from SRAM. The
per-word semantics are `repro.ppuvm.interp.make_branches`/`step_word`,
shared verbatim with the scan interpreter, so the two executors cannot
drift; bit-equality across random programs is enforced by
``tests/test_ppuvm_fuzz.py``.

Operand tiling (grid = (R//rb, C//cb)):
  weights/qc/qa/noise  [R, C] int32   -> (rb, cb) row tiles
  rates_fx             [1, C] int32   -> (1, cb) column tiles (pre-sat
                       Q8.8 — digitized once on the host side of the
                       kernel so every executor consumes identical ints)
  mod                  [n_mod, C] i32 -> (n_mod, cb) column tiles
Outputs: new weights (rb, cb) int32 and the final register file
  (N_REGS, rb, cb) — the program's scratch readout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.ppuvm import isa
from repro.ppuvm.interp import make_branches, step_word


def _kernel(words_ref, w_ref, qc_ref, qa_ref, rates_ref, mod_ref, noise_ref,
            wout_ref, regs_ref, *, n_words: int):
    lane = w_ref.shape                                   # (rb, cb)
    rates_fx = jnp.broadcast_to(rates_ref[...], lane)
    mod = jnp.broadcast_to(mod_ref[...][:, None, :],
                           (mod_ref.shape[0], *lane))
    branches = make_branches(lane, qc_ref[...], qa_ref[...], rates_fx, mod,
                             noise_ref[...])
    regs0 = jnp.zeros((isa.N_REGS, *lane), jnp.int32)

    def body(i, carry):
        regs, wmem = carry
        return step_word(branches, regs, wmem, words_ref[i])

    regs, wmem = jax.lax.fori_loop(0, n_words, body, (regs0, w_ref[...]))
    wout_ref[...] = wmem
    regs_ref[...] = regs


@functools.partial(jax.jit,
                   static_argnames=("rb", "cb", "interpret"))
def run_program_pallas(words, weights, qc, qa, rates_fx, mod, noise, *,
                       rb: int = 64, cb: int = 128,
                       interpret: bool = False):
    """words [P] int32; weights/qc/qa/noise [R, C] int32; rates_fx [C]
    int32 (already saturated Q8.8); mod [n_mod, C] int32. Returns
    (new_weights int32 [R, C], regs int32 [N_REGS, R, C])."""
    R, C = weights.shape
    rb = min(rb, R)
    cb = min(cb, C)
    assert R % rb == 0 and C % cb == 0, (R, C, rb, cb)
    n_mod = mod.shape[0]
    # index maps get the scalar-prefetch ref appended to the grid indices
    row_spec = pl.BlockSpec((rb, cb), lambda i, j, words_ref: (i, j))
    col_spec = pl.BlockSpec((1, cb), lambda i, j, words_ref: (0, j))
    mod_spec = pl.BlockSpec((n_mod, cb), lambda i, j, words_ref: (0, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(R // rb, C // cb),
        in_specs=[row_spec, row_spec, row_spec, col_spec, mod_spec,
                  row_spec],
        out_specs=[row_spec,
                   pl.BlockSpec((isa.N_REGS, rb, cb),
                                lambda i, j, words_ref: (0, i, j))],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, n_words=int(words.shape[0])),
        name="ppuvm_exec",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((R, C), jnp.int32),
                   jax.ShapeDtypeStruct((isa.N_REGS, R, C), jnp.int32)],
        interpret=interpret,
    )(words.astype(jnp.int32), weights.astype(jnp.int32),
      qc.astype(jnp.int32), qa.astype(jnp.int32),
      rates_fx[None].astype(jnp.int32), mod.astype(jnp.int32),
      noise.astype(jnp.int32))
    return out[0], out[1]
