"""Jit'd public wrappers for the synray_sparse kernel.

Two entry points:

``sparse_window``
    The compute on already-regrouped [.., T, K] event records — kernel or
    jnp ref, selected by ``impl`` like every other kernel wrapper.

``synaptic_current_sparse``
    The full event-sparse path on the same [N, T, R] folded operands the
    dense ``synray`` wrapper takes: pack the window into the compact
    event stream (``repro.core.events``), regroup per step, compute.
    Capacities ``max_events``/``k_cap`` are static (they size the jitted
    program); windows that overflow them silently drop records — callers
    that cannot prove the window fits must gate on
    ``repro.core.events.window_stats`` and fall back to the dense path
    (``repro.core.synapse.synaptic_current_window(sparse="auto")`` does).

Operands may carry an arbitrary instance prefix via the callers' fold
(see ``repro.kernels``): the kernel runs the fleet on its instance grid
axis, the ref path vmaps.
"""
from __future__ import annotations

import functools

import jax

from repro.core import events as ev_mod
from repro.kernels.synray_sparse.kernel import sparse_window_pallas
from repro.kernels.synray_sparse.ref import sparse_window_ref

# jitted once at import — same rationale as the synray wrapper
_ref_jit = jax.jit(sparse_window_ref)
_ref_vmap_jit = jax.jit(jax.vmap(sparse_window_ref))


def sparse_window(rows_tk, addr_tk, eff_tk, weights, addresses,
                  impl: str = "auto", **block_kw):
    """impl: auto | interpret | ref. Record operands [.., T, K],
    weights/addresses [.., R, C] (2-D = single instance).

    ``auto`` is the jnp form on every backend: the kernel's row gather
    does not lower on TPU (see ``kernel.py``), so ``pallas`` raises."""
    if impl == "pallas":
        raise NotImplementedError(
            "synray_sparse has no native TPU lowering: Mosaic refuses the "
            "in-kernel row gather ('Shape mismatch in input, indices and "
            "output'); TPU runs the sparse path's jnp form (impl='ref')")
    if impl in ("auto", "ref"):
        if rows_tk.ndim == 2:
            return _ref_jit(rows_tk, addr_tk, eff_tk, weights, addresses)
        return _ref_vmap_jit(rows_tk, addr_tk, eff_tk, weights, addresses)
    return sparse_window_pallas(rows_tk, addr_tk, eff_tk, weights,
                                addresses,
                                interpret=(impl == "interpret"), **block_kw)


@functools.partial(jax.jit, static_argnames=("max_events", "k_cap"))
def _pack_regroup(row_events_t, event_addr_t, *, max_events, k_cap):
    T = row_events_t.shape[1]

    def one(ev, ad):
        stream = ev_mod.pack_events(ev, ad, max_events)
        return ev_mod.regroup_events(stream, T, k_cap)

    return jax.vmap(one)(row_events_t, event_addr_t)


def synaptic_current_sparse(row_events_t, event_addr_t, weights, addresses,
                            *, max_events: int, k_cap: int,
                            impl: str = "auto", **block_kw):
    """row_events_t [N, T, R] f32 (0 = silent, else efficacy);
    event_addr_t [N, T, R] int; weights/addresses [N, R, C] i8
    -> [N, T, C] f32. Drops events beyond the static capacities — see
    module docstring."""
    rows_tk, addr_tk, eff_tk = _pack_regroup(
        row_events_t, event_addr_t, max_events=max_events, k_cap=k_cap)
    return sparse_window(rows_tk, addr_tk, eff_tk, weights, addresses,
                         impl=impl, **block_kw)
