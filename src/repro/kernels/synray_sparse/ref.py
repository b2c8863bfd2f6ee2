"""Pure-jnp oracle for the synray_sparse kernel — and the CPU hot path.

``sparse_window_ref`` consumes the per-step [T, K] regrouped event records
(``repro.core.events.pack_regrouped``, the records of ``regroup_events``):
gather each step's fired weight rows, apply the 6-bit address match per
gathered record, and contract the K record slots against the efficacies.

Bit-exactness contract (the reason this path may replace the dense one):
XLA:CPU reduces a contraction as one in-order FMA chain per output
element, so (a) terms that are exactly zero are exact no-ops in the chain
(``fma(0 * w, acc) == acc``), and (b) the chain does not depend on the
other rows/columns of the product. Dropping the silent rows while keeping
the fired ones in row order — which the t-major stream regrouping
guarantees — therefore reproduces the dense matmul BIT-identically, as
long as the reduction runs through the same dot machinery. Hence the
einsum below, never a hand-rolled accumulation loop (separate mul+add
rounds differently than the fused multiply-add). Asserted exactly, over a
0%..100% density sweep, in tests/test_sparse.py.

This is also the sparse path's form on TPU (the Pallas twin does not
lower there), so the einsum asks for HIGHEST precision: a TPU f32 dot
otherwise rounds its operands to bfloat16, and the STP efficacies would
lose 16 mantissa bits against the f32 dense path. XLA:CPU computes f32
dots in f32 either way, so the CPU bits are unchanged.
"""
import jax
import jax.numpy as jnp


def sparse_window_ref(rows_tk, addr_tk, eff_tk, weights, addresses):
    """rows_tk/addr_tk [T, K] i32; eff_tk [T, K] f32 (0 in empty slots);
    weights/addresses [R, C] i8 -> [T, C] f32."""
    wg = weights[rows_tk].astype(jnp.float32)              # [T, K, C]
    match = (addresses[rows_tk] == addr_tk[..., None]).astype(jnp.float32)
    return jnp.einsum("tk,tkc->tc", eff_tk, wg * match,
                      precision=jax.lax.Precision.HIGHEST)
