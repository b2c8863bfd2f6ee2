"""Public wrapper for the Pallas tile-VM executor.

``run_program_tiled`` accepts the same operands as
``repro.ppuvm.interp.run_program_jax`` (arbitrary instance prefix,
broadcastable qc/qa/noise, float rate counters, optional mod/noise) and
routes the 2-D core through ``kernel.run_program_pallas``; a leading
instance prefix is folded by nested vmap like the other kernel wrappers.

Host-side preparation mirrors ``interp.prepare_operands`` bit-for-bit
(rate saturation, Q8.8 digitization conventions), so the kernel consumes
exactly the integers every other executor sees.

TPU: the kernel does not compile for the chip. The shared ``step_word``
indexes the register file by a traced register number
(``regs[ra % N_REGS]``), and the TPU compiler refuses it: "Unimplemented
primitive in Pallas TPU lowering for KernelType.TC: dynamic_slice" (v5e,
at 256x512 and 32x16). So only interpret mode runs, as a CPU
cross-check in the differential fuzz; the native call raises with that
reason. The trace-time specializer is the executor for the chip.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.ppuvm_exec.kernel import run_program_pallas
from repro.ppuvm import isa
from repro.ppuvm.interp import rates_to_fixed


def run_program_tiled(words, weights, qc, qa, rates, mod=None, noise=None,
                      *, rb: int = 64, cb: int = 128,
                      interpret: bool = False):
    """Same signature/returns as ``interp.run_program_jax``:
    (weights_out int32 [..., R, C], regs int32 [N_REGS, ..., R, C])."""
    if not interpret:
        raise NotImplementedError(
            "the ppuvm_exec kernel does not lower on TPU: the register-file "
            "read regs[ra % N_REGS] is a dynamic_slice, 'Unimplemented "
            "primitive in Pallas TPU lowering for KernelType.TC: "
            "dynamic_slice'; use executor='specialized' (or "
            "'pallas_interpret' on CPU)")
    lane_shape = weights.shape
    words = jnp.asarray(words, jnp.int32)
    weights = weights.astype(jnp.int32)
    qc = jnp.broadcast_to(qc, lane_shape).astype(jnp.int32)
    qa = jnp.broadcast_to(qa, lane_shape).astype(jnp.int32)
    rates_fx = rates_to_fixed(rates)                     # [..., C]
    rates_fx = jnp.broadcast_to(rates_fx, (*lane_shape[:-2],
                                           lane_shape[-1]))
    if mod is None:
        mod = jnp.zeros((1, *lane_shape[:-2], lane_shape[-1]), jnp.int32)
    mod = jnp.broadcast_to(mod, (mod.shape[0], *lane_shape[:-2],
                                 lane_shape[-1])).astype(jnp.int32)
    if noise is None:
        noise = jnp.zeros(lane_shape, jnp.int32)
    noise = jnp.broadcast_to(noise, lane_shape).astype(jnp.int32)

    def fn(w, c, a, r, m, n):
        return run_program_pallas(words, w, c, a, r, m, n, rb=rb, cb=cb,
                                  interpret=interpret)

    # peel one instance dim per vmap: operands carry the prefix at axis 0,
    # mod at axis 1 (slots lead); regs gain the prefix at axis 1 (N_REGS
    # leads), matching interp's [N_REGS, ..., R, C] convention
    for _ in range(weights.ndim - 2):
        fn = jax.vmap(fn, in_axes=(0, 0, 0, 0, 1, 0), out_axes=(0, 1))
    return fn(weights, qc, qa, rates_fx, mod, noise)
