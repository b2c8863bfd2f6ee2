"""Pallas TPU kernels for the machine model's compute hot-spots.

Each kernel has a pure-jnp oracle (ref.py) and a jit'd wrapper (ops.py).
On CPU the tests check them against the oracle in interpret mode; on a
TPU they compile to Mosaic custom calls (``tests/test_tpu_compile.py``
compiles each for a described v5e at 256x512 and 32x16, and
``chip_smoke.py`` runs them on the chip):

  synray      event x 6-bit-weight synaptic-current matmul with in-kernel
              address matching (the synapse array's event path)
  synray_sparse
              the event-sparse twin of synray: gather-accumulates only
              fired rows from a compact [T, K] record grid
              (repro.core.events) — O(T*K*C) instead of O(T*R*C), and
              BIT-identical to the dense path on CPU (see its ref.py);
              auto-selected per window by measured event density in
              ``synapse.synaptic_current_window(sparse="auto")``. Its
              in-kernel row gather does not lower on TPU, so there the
              sparse path runs the jnp form; the kernel runs only in
              interpret mode, as a CPU cross-check
  ppuvm_exec  the PPU-VM tile executor; interpret mode only (its register
              file read does not lower on TPU, see its ops.py)
  corr        T-step fused correlation-sensor update: decay + outer-product
              accumulation entirely in VMEM (T x fewer HBM round trips)
  ppu_update  the PPU vector-unit inner loop: CADC digitization ->
              eligibility -> R-STDP -> saturating 6-bit weight write-back,
              row-parallel

Implementation selection
------------------------
Every ops.py wrapper takes ``impl``:

  auto        pallas when ``jax.default_backend() == "tpu"``, else ref
  pallas      the native Pallas kernel (TPU; synray_sparse and ppuvm_exec
              raise)
  interpret   the Pallas kernel under the interpreter (CPU validation)
  ref         the module-level-jitted jnp oracle

The emulation hot path consumes these through ``AnnCore`` (see
repro.core.anncore): ``AnnCore(cfg, inst, backend="fused")`` hoists the
correlation-sensor update out of the per-dt scan (one ``corr`` call per
trial), batches the whole trial's synaptic currents through ``synray``
(time as the batch axis), and ``VectorUnit.apply_rstdp`` routes the
standard R-STDP write-back through ``ppu_update`` (the §5 Dale-signed
rule stays on the generic VM path). ``backend="oracle"`` keeps
the literal per-step semantics as ground truth; ``backend="auto"`` selects
the fused path (the blocked ``neuron_scan`` variant on TPU), mirroring
the impl auto-selection above.

Instance grid axis
------------------
The multi-instance fleet (a batch of independent virtual chips) maps onto
the kernels as a real leading grid axis, not a nested ``jax.vmap`` fold:
the wrappers collapse an arbitrary instance prefix into one N axis with
the helpers below, and each kernel's grid is ``(N, ...tile axes)`` — one
kernel launch for the whole fleet. ``repro.parallel.sharding.Ax.INSTANCE``
names the same axis for the mesh (instances shard over the data dims), so
the grid axis and the sharding axis coincide by construction.
"""
from __future__ import annotations

import math

import jax.numpy as jnp


def tile(block: int, dim: int) -> int:
    """The requested block when it tiles ``dim``, else the whole dim. A
    block equal to the array dim satisfies the TPU tiling rule (last two
    block dims divisible by 8 and 128, or equal to the array's) at any
    size, so e.g. the 16-column paper chip runs as one lane block."""
    return block if block < dim and dim % block == 0 else dim


def fold_instance(x, n_core: int):
    """[*prefix, *core] -> [N, *core] with N = prod(prefix) (N=1 when the
    prefix is empty). ``n_core`` is the number of trailing core dims."""
    core = x.shape[x.ndim - n_core:]
    return x.reshape(math.prod(x.shape[:x.ndim - n_core]), *core)


def unfold_instance(y, prefix):
    """Inverse of ``fold_instance``: [N, *core] -> [*prefix, *core]."""
    return y.reshape(*prefix, *y.shape[1:])


def fold_instance_time(x, n_core: int):
    """[T, *prefix, *core] -> [N, T, *core]: time-major window operands
    (event streams, current windows) fold their instance prefix in front
    of the time axis for the kernel instance grid."""
    n_prefix = x.ndim - 1 - n_core
    x = jnp.moveaxis(x, 0, n_prefix)
    return fold_instance(x, n_core + 1)


def unfold_instance_time(y, prefix):
    """Inverse of ``fold_instance_time``: [N, T, *core] -> [T, *prefix,
    *core]."""
    y = y.reshape(*prefix, *y.shape[1:])
    return jnp.moveaxis(y, len(prefix), 0)
