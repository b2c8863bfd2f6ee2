"""Pallas kernel: event-sparse synapse-array path.

i[n, t, c] = sum_k eff[n, t, k] * w[n, rows[n, t, k], c]
                 * (addr_store[n, rows[n, t, k], c] == addr[n, t, k])

Hardware adaptation (DESIGN.md): on BSS-2 only the rows that actually
received an event ripple current into the array — the dense matmul is the
TPU-friendly *approximation* of that, and this kernel is the faithful one:
the [T, K] regrouped event records (``repro.core.events``) gather exactly
the fired weight rows, the 6-bit address comparison runs per gathered
record, and the K record slots contract against the efficacies. Work is
O(T * K * C) instead of O(T * R * C) — at 1% density with K ~ R/16 that is
an order of magnitude fewer MACs.

The grid is (instances, column blocks): the whole [T, K] record grid plus
the [R, cb] weight/address tiles live in VMEM, and the contraction is ONE
batched [T, K] x [T, K, cb] dot — the same einsum as the jnp ref, so the
per-element reduction chain (and therefore every bit, see ref.py) is
preserved; empty record slots carry eff == 0 and are exact no-ops in the
FMA chain. No K-axis grid blocking: splitting K would re-order the
reduction and break the bit contract. The leading ``n`` is the instance
grid axis shared with the other kernels (see ``repro.kernels``); 2-D
record operands are promoted to N=1.

TPU: this kernel does NOT compile for the chip. Mosaic refuses the
in-kernel ``jnp.take`` row gather ("Shape mismatch in input, indices and
output", v5e, at 256x512 and 32x16 alike). On TPU the sparse path
therefore runs its jnp form (``ref.py``: an XLA gather and a
HIGHEST-precision einsum) — decided by name in ``ops.sparse_window``,
where ``impl="pallas"`` raises. The kernel stays as the interpret-mode
cross-check of the gather-accumulate contract on CPU
(tests/test_sparse.py). Lowering the gather with scalar-prefetched row
indices is the open alternative (ROADMAP, Speed).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(rows_ref, addr_ref, eff_ref, w_ref, st_ref, out_ref):
    rows = rows_ref[0]                                  # [T, K] i32
    T, K = rows.shape
    flat = rows.reshape(-1)
    wg = jnp.take(w_ref[0], flat, axis=0)               # [T*K, cb] i8
    sg = jnp.take(st_ref[0], flat, axis=0)
    wg = wg.reshape(T, K, -1).astype(jnp.float32)
    match = (sg.reshape(T, K, -1) == addr_ref[0][:, :, None]
             ).astype(jnp.float32)
    out_ref[0] = jnp.einsum("tk,tkc->tc", eff_ref[0], wg * match)


@functools.partial(jax.jit, static_argnames=("cb", "interpret"))
def sparse_window_pallas(rows_tk, addr_tk, eff_tk, weights, addresses, *,
                         cb: int = 128, interpret: bool = False):
    """rows_tk/addr_tk: [N, T, K] i32; eff_tk: [N, T, K] f32;
    weights/addresses: [N, R, C] i8. Returns [N, T, C] f32. 2-D operands
    (no instance axis) are promoted to N=1 and squeezed back."""
    squeeze = rows_tk.ndim == 2
    if squeeze:
        rows_tk, addr_tk, eff_tk = rows_tk[None], addr_tk[None], eff_tk[None]
        weights, addresses = weights[None], addresses[None]
    N, T, K = rows_tk.shape
    R, C = weights.shape[-2:]
    cb = min(cb, C)
    assert C % cb == 0, (C, cb)
    grid = (N, C // cb)
    rec_spec = pl.BlockSpec((1, T, K), lambda n, j: (n, 0, 0))
    w_spec = pl.BlockSpec((1, R, cb), lambda n, j: (n, 0, j))
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[rec_spec, rec_spec, rec_spec, w_spec, w_spec],
        out_specs=pl.BlockSpec((1, T, cb), lambda n, j: (n, 0, j)),
        out_shape=jax.ShapeDtypeStruct((N, T, C), jnp.float32),
        interpret=interpret,
    )(rows_tk, addr_tk, eff_tk, weights, addresses)
    return out[0] if squeeze else out
