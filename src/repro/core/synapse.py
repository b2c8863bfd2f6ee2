"""Synapse array: 6-bit weights + 6-bit address matching (paper §2.1).

Each synapse stores a 6-bit weight and a 6-bit address. An event on a row
carries a source address; the synapse forwards current only when the stored
address matches. Current amplitude = weight * DAC gain (with per-column
mismatch) * STP efficacy of the driver.

The hot operation — events x weights -> per-column synaptic currents — is a
masked int-weight matmul; the Pallas kernel ``repro.kernels.synray``
implements the fused 6-bit dequant + matmul for TPU, and this module's
``synaptic_current`` is its jnp oracle (used on CPU and in tests).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import events

WMAX = 63  # 6-bit


class SynapseArray(NamedTuple):
    weights: jnp.ndarray    # [..., rows, cols] int8 in [0, 63]
    addresses: jnp.ndarray  # [..., rows, cols] int8 in [0, 63]


def init_array(shape_prefix, rows, cols, key=None) -> SynapseArray:
    w = jnp.zeros((*shape_prefix, rows, cols), jnp.int8)
    a = jnp.zeros((*shape_prefix, rows, cols), jnp.int8)
    return SynapseArray(weights=w, addresses=a)


def synaptic_current(weights, addresses, row_events, event_addr, gain):
    """Per-column synaptic current from one event step.

    weights/addresses: [..., R, C] int8; row_events: [..., R] float (0/1 x
    STP efficacy); event_addr: [..., R] int8 (address carried by the event
    on that row); gain: scalar or [..., C] DAC gain.
    Returns [..., C] float32.
    """
    match = (addresses == event_addr[..., None]).astype(jnp.float32)
    w_eff = weights.astype(jnp.float32) * match
    i = jnp.einsum("...rc,...r->...c", w_eff, row_events.astype(jnp.float32))
    return i * gain


# Density below which "auto" routes a window through the event-sparse
# path. The measured dense/sparse crossover on the CPU container sits
# between 50% and 100% density (BENCH_pr6_sparse.json: 1.24x at p=0.5,
# 0.67x at p=1.0), but the default capacities scale with the threshold
# and the static sparse cost is O(T * k_cap * C) — 0.05 keeps that well
# under the dense work while covering the ~4-5x regime at p <= 5%.
SPARSE_THRESHOLD = 0.05
# With ``const_addr`` the dense alternative is the once-resolved PLAIN
# matmul — no [T, R, C] address-mask materialization — so the sparse
# path must clear a lower bar before it wins. "auto" therefore sizes
# its default capacities from this lower threshold when const_addr is
# set: windows in the (0.02, 0.05] density band that used to route
# sparse now overflow the tighter budget and take the (cheaper-here)
# dense fallback. Regression:
# tests/test_sparse.py::TestAutoGate::test_const_addr_lowers_crossover.
SPARSE_THRESHOLD_CONST_ADDR = 0.02
# Static work floor (T * R * C MACs): below it the dense matmul is so
# cheap that packing overhead and the runtime branch can never pay off,
# so sparse="auto" compiles to the pure dense program (keeps e.g. the
# 16 x 16 §5 experiment byte-for-byte the same program as before).
SPARSE_MIN_DENSE_WORK = 2 * 1024 * 1024


def _dense_window(weights, addresses, row_events_t, event_addr_t, gain,
                  impl, const_addr, bb):
    """The dense whole-window path (kernel or broadcasting oracle)."""
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "ref"
    if impl == "ref":
        if const_addr:
            match = (addresses == event_addr_t[0][..., None]
                     ).astype(jnp.float32)
            w_eff = weights.astype(jnp.float32) * match
            if weights.ndim == 2:     # no instance prefix: plain matmul
                i = row_events_t.astype(jnp.float32) @ w_eff
            else:
                i = jnp.einsum("t...r,...rc->t...c",
                               row_events_t.astype(jnp.float32), w_eff)
            return i * gain
        return synaptic_current(weights, addresses, row_events_t,
                                event_addr_t, gain)
    from repro.kernels import (fold_instance, fold_instance_time,
                               unfold_instance_time)
    from repro.kernels.synray import ops as synray_ops

    # time is the kernel's batch axis; pad the window up to the batch
    # block instead of shrinking the block to a divisor of T (the old
    # ``next(d for d in (8, 4, 2, 1) ...)`` silently degraded to bb=1 for
    # any odd T). Batch rows are independent, so zero-event pad steps are
    # exact and sliced off after the call.
    T = row_events_t.shape[0]
    if bb is None:
        bb = min(8, T)
    pad = -T % bb
    if pad:
        row_events_t = jnp.concatenate(
            [row_events_t,
             jnp.zeros((pad, *row_events_t.shape[1:]),
                       row_events_t.dtype)], axis=0)
        event_addr_t = jnp.concatenate(
            [event_addr_t,
             jnp.zeros((pad, *event_addr_t.shape[1:]),
                       event_addr_t.dtype)], axis=0)
    prefix = weights.shape[:-2]
    i = synray_ops.synaptic_current(
        fold_instance_time(row_events_t.astype(jnp.float32), 1),
        fold_instance_time(event_addr_t, 1),
        fold_instance(weights, 2), fold_instance(addresses, 2),
        impl=impl, bb=bb)
    i = unfold_instance_time(i, prefix)
    if pad:
        i = i[:T]
    return i * gain


def _sparse_window(weights, addresses, row_events_t, event_addr_t, gain,
                   impl, max_events, k_cap):
    """The event-sparse whole-window path (repro.kernels.synray_sparse).

    Packs the window into the compact event stream and gather-accumulates
    only fired rows — BIT-identical to the dense path as long as the
    window fits the static capacities (overflow drops records; the
    ``sparse="auto"`` gate in ``synaptic_current_window`` guarantees the
    fit before routing here)."""
    # the sparse kernel runs only as the explicit CPU cross-check: on TPU
    # (kernel_impl "auto"/"pallas") this path is its jnp form, because the
    # kernel's row gather does not lower there (synray_sparse/kernel.py)
    impl = "interpret" if impl == "interpret" else "ref"
    from repro.kernels import (fold_instance, fold_instance_time,
                               unfold_instance_time)
    from repro.kernels.synray_sparse import ops as sparse_ops

    prefix = weights.shape[:-2]
    i = sparse_ops.synaptic_current_sparse(
        fold_instance_time(row_events_t.astype(jnp.float32), 1),
        fold_instance_time(event_addr_t, 1),
        fold_instance(weights, 2), fold_instance(addresses, 2),
        max_events=max_events, k_cap=k_cap, impl=impl)
    return unfold_instance_time(i, prefix) * gain


def synaptic_current_window(weights, addresses, row_events_t, event_addr_t,
                            gain, impl: str = "auto",
                            const_addr: bool = False,
                            sparse: str = "auto",
                            sparse_threshold: float = None,
                            max_events: int = None, k_cap: int = None,
                            bb: int = None, telemetry=None):
    """Whole-window synaptic currents: [T, ..., R] events -> [T, ..., C].

    Weights and addresses are constant between PPU writes, so the per-step
    masked matmul collapses into ONE time-batched event x weight matmul:
    time becomes the batch axis of the ``repro.kernels.synray`` Pallas
    kernel (address matching stays in-kernel, so per-step event addresses
    remain fully general). On CPU the broadcasting jnp oracle runs instead.
    A leading instance prefix on ``weights`` maps onto the kernel's
    instance grid axis (one launch for the whole fleet — see
    ``repro.kernels``); the oracle broadcasts natively.

    ``const_addr=True`` asserts the event address on each row is the same
    at every step of the window (true whenever each driver row carries a
    single source, e.g. the §5 experiment). The address-match mask is then
    resolved ONCE into an effective weight matrix and the whole window is
    a plain [T, R] x [R, C] matmul — no [T, R, C] mask materialization.

    The machine is event-driven, and at low firing rates the dense matmul
    does orders of magnitude more MACs than the events justify. ``sparse``
    selects the event-sparse path (``repro.kernels.synray_sparse``: pack
    the window into a compact event stream, gather-accumulate only fired
    rows — BIT-identical to the dense path by the in-order-FMA argument in
    its ref.py):

      "auto"    (default) route through sparse when the window provably
                fits the event capacities — a runtime ``lax.cond`` on the
                measured event census, so overflow NEVER drops records (it
                falls back to dense). Windows below the static
                ``SPARSE_MIN_DENSE_WORK`` floor compile to the pure dense
                program with zero switch overhead.
      "never"   always dense (the pre-sparse behavior).
      "always"  force sparse — the caller promises the window fits
                ``max_events``/``k_cap``; overflow silently drops events
                (see tests/test_sparse.py's divergence contract).

    ``sparse_threshold`` sizes the default capacities: ``max_events`` ~
    threshold * T * R total records and ``k_cap`` per-step records, both
    overridable. Its default is ``const_addr``-aware: ``SPARSE_THRESHOLD``
    normally, the lower ``SPARSE_THRESHOLD_CONST_ADDR`` when the dense
    alternative is the once-resolved plain matmul — the auto gate then
    hands the (0.02, 0.05] density band back to dense, where the
    const_addr matmul wins. ``impl`` selects the
    kernel implementation for whichever path runs (auto | pallas |
    interpret | ref). As convenience aliases, ``impl="dense"`` /
    ``impl="sparse"`` force the respective path with auto kernels.

    ``bb`` overrides the dense kernel's time-batch block (default 8; T is
    padded up with zero-event steps when it does not divide).

    ``telemetry`` threads an ``repro.obs.trace.Telemetry`` pytree (or
    ``None`` = off): routing decisions are counted — static dense/sparse
    routes, runtime census-gate outcomes, and capacity-overflow fallbacks
    to dense (previously silent). With telemetry the return value is
    ``(currents, telemetry)``; the currents themselves are untouched (the
    counters only read the census the gate already computes), so on/off
    stays bit-identical.
    """
    from repro.obs import trace as obs_trace
    if impl == "dense":
        impl, sparse = "auto", "never"
    elif impl == "sparse":
        impl, sparse = "auto", "always"
    elif impl.startswith("sparse_"):
        impl, sparse = impl[len("sparse_"):], "always"
    if sparse not in ("auto", "never", "always"):
        raise ValueError(f"unknown sparse mode {sparse!r}")

    T = row_events_t.shape[0]
    R = row_events_t.shape[-1]
    C = weights.shape[-1]
    if sparse == "auto" and T * R * C < SPARSE_MIN_DENSE_WORK:
        sparse = "never"
    if sparse == "never":
        i = _dense_window(weights, addresses, row_events_t,
                          event_addr_t, gain, impl, const_addr, bb)
        if telemetry is None:
            return i
        return i, obs_trace.count_route(telemetry, sparse=False)

    if sparse_threshold is not None:
        thr = sparse_threshold
    else:
        thr = SPARSE_THRESHOLD_CONST_ADDR if const_addr else SPARSE_THRESHOLD
    if max_events is None:
        max_events = events.default_max_events(T, R, thr)
    if k_cap is None:
        k_cap = events.default_k_cap(R, thr)
    if sparse == "always":
        i = _sparse_window(weights, addresses, row_events_t,
                           event_addr_t, gain, impl, max_events, k_cap)
        if telemetry is None:
            return i
        return i, obs_trace.count_route(telemetry, sparse=True)

    n, kmax = events.window_stats(row_events_t)
    fits = events.census_fits(n, kmax, max_events, k_cap)
    i = jax.lax.cond(
        fits,
        lambda: _sparse_window(weights, addresses, row_events_t,
                               event_addr_t, gain, impl, max_events,
                               k_cap),
        lambda: _dense_window(weights, addresses, row_events_t,
                              event_addr_t, gain, impl, const_addr, bb))
    if telemetry is None:
        return i
    return i, obs_trace.count_gate(telemetry, fits, n, kmax)


def quantize_weight(w_float):
    """Saturating 6-bit write (the PPU's vector-store semantics)."""
    return jnp.clip(jnp.round(w_float), 0, WMAX).astype(jnp.int8)
