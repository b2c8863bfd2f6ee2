"""Compact event streams for the event-sparse synaptic path.

The chip is event-driven: synapse drivers forward address-matched events,
and the silicon verification budgets the event bus at ~0.4M events/s
(fig8 reproduces ~0.4M events/s on the software path). The dense
emulation nevertheless pays the full [T, R] x [R, C] matmul per window
even when almost no rows fired. This module is the packing layer of the
event-sparse paths: a window's [T, R] row events + per-row event
addresses become a compact fixed-capacity stream of ``(t, row, addr,
efficacy)`` records — the software analogue of the packed event frames
SpikeHard's ``dma_controller.v`` streams.

The sparse synaptic route (``repro.kernels.synray_sparse``) consumes the
records regrouped per step, a [T, K] grid. ``pack_regrouped`` builds that
grid straight from the window, by per-step ordinals and one-hot
reductions, with no scatter; it gives exactly the records of
``pack_events`` followed by ``regroup_events``. The stream itself remains
the inter-chip router's per-link transport (``pack_events_batch``) and
the reference the tests hold ``pack_regrouped`` to.

Everything here jits: the capacity ``max_events`` is static and a
validity mask marks the live records. Records are t-major (sorted by
timestep, rows ascending within a step) — the order the event bus would
deliver them, and the order the sparse kernels rely on for bit-exact
accumulation against the dense matmul. ``n_events`` keeps the TRUE
event count even when it exceeds the capacity, so callers can detect
overflow and fall back to the dense path (``synapse.
synaptic_current_window(sparse="auto")`` does exactly that); a stream
packed over capacity silently DROPS the tail records — forcing the
sparse path without the fallback is a broken promise, proven divergent
by the contract test in ``tests/test_sparse.py``.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp


class EventStream(NamedTuple):
    """Fixed-capacity window event stream (capacity E = ``t.shape[-1]``)."""
    t: jnp.ndarray         # [E] int32 timestep of each record
    row: jnp.ndarray       # [E] int32 driver row carrying the event
    addr: jnp.ndarray      # [E] int32 6-bit source address of the event
    eff: jnp.ndarray       # [E] float32 STP efficacy forwarded with it
    valid: jnp.ndarray     # [E] bool   live-record mask
    n_events: jnp.ndarray  # [] int32   TRUE count (may exceed capacity)

    @property
    def capacity(self) -> int:
        return self.t.shape[-1]


def pack_events(row_events_t, event_addr_t, max_events: int) -> EventStream:
    """[T, R] events (0 = silent, else efficacy) -> t-major EventStream.

    ``max_events`` is the static stream capacity. Records beyond it are
    dropped (``n_events`` still reports the true count — check
    ``overflowed`` before trusting a forced-sparse result).
    """
    T, R = row_events_t.shape
    flat_eff = row_events_t.reshape(-1).astype(jnp.float32)
    flat_addr = event_addr_t.reshape(-1).astype(jnp.int32)
    fired = flat_eff != 0.0
    # t-major ordinal of every fired slot; silent slots and the overflow
    # tail land on index E (out of bounds -> dropped by the scatters)
    ordinal = jnp.cumsum(fired.astype(jnp.int32)) - 1
    n = jnp.sum(fired.astype(jnp.int32))
    dst = jnp.where(fired & (ordinal < max_events), ordinal, max_events)
    src = jnp.arange(T * R, dtype=jnp.int32)
    z = jnp.zeros((max_events,), jnp.int32)
    t = z.at[dst].set(src // R, mode="drop")
    row = z.at[dst].set(src % R, mode="drop")
    addr = z.at[dst].set(flat_addr, mode="drop")
    eff = jnp.zeros((max_events,), jnp.float32).at[dst].set(flat_eff,
                                                            mode="drop")
    valid = jnp.arange(max_events, dtype=jnp.int32) < n
    return EventStream(t=t, row=row, addr=addr, eff=eff, valid=valid,
                       n_events=n)


def unpack_events(stream: EventStream, T: int, R: int
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Inverse of ``pack_events`` (up to dropped overflow records).

    Returns ``(row_events_t, event_addr_t)``: efficacies scattered back
    onto the [T, R] grid, and the event addresses at fired slots (silent
    slots carry address 0 — the stream only transports addresses WITH
    events, exactly like the hardware bus).
    """
    dst = jnp.where(stream.valid, stream.t * R + stream.row, T * R)
    ev = jnp.zeros((T * R,), jnp.float32).at[dst].set(stream.eff,
                                                      mode="drop")
    ad = jnp.zeros((T * R,), jnp.int32).at[dst].set(stream.addr,
                                                    mode="drop")
    return ev.reshape(T, R), ad.reshape(T, R)


def overflowed(stream: EventStream) -> jnp.ndarray:
    """True when the window produced more events than the capacity."""
    return stream.n_events > stream.capacity


def step_counts(stream: EventStream, T: int) -> jnp.ndarray:
    """[T] record count per timestep of the *stored* records."""
    seg = jnp.where(stream.valid, stream.t, T)
    return jnp.zeros((T + 1,), jnp.int32).at[seg].add(1,
                                                      mode="drop")[:T]


def step_overflowed(stream: EventStream, T: int, k_cap: int) -> jnp.ndarray:
    """True when regrouping at ``k_cap`` would drop records.

    ``overflowed`` only flags *total*-capacity overflow; a stream can fit
    ``max_events`` while a single step holds more than ``k_cap`` records —
    ``regroup_events`` then drops that step's tail silently. This is the
    per-step twin. It also returns True whenever records are already
    missing (``n_events`` exceeds the stored records — total-capacity
    overflow or a ``truncate_stream`` cut): the dropped tail could have
    landed on any step, so the stored per-step counts understate the
    truth.
    """
    missing = stream.n_events > jnp.count_nonzero(
        stream.valid).astype(jnp.int32)
    return missing | (jnp.max(step_counts(stream, T)) > k_cap)


def census_fits(n_events, k_max, max_events: int, k_cap: int) -> jnp.ndarray:
    """The shared no-drop predicate: a window whose event census is
    ``(n_events, k_max)`` packs AND regroups losslessly into capacities
    ``(max_events, k_cap)``. Gates both the density auto-switch
    (``synapse.synaptic_current_window(sparse="auto")``) and the wafer
    router's per-link budget — one definition, so the two fallback paths
    cannot drift apart."""
    return (n_events <= max_events) & (k_max <= k_cap)


# ---------------------------------------------------------------------------
# Batched streams — the inter-chip router's per-link transport
# ---------------------------------------------------------------------------

def pack_events_batch(row_events_bt, event_addr_bt,
                      max_events: int) -> EventStream:
    """[B, T, R] grids -> EventStream with [B, E] leaves ([B] counts).

    One fixed-capacity stream per leading-batch element — the wafer
    router packs one stream per inter-chip link this way."""
    return jax.vmap(pack_events, in_axes=(0, 0, None))(
        row_events_bt, event_addr_bt, max_events)


def unpack_events_batch(stream: EventStream, T: int, R: int
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Inverse of ``pack_events_batch``: [B, E] stream leaves ->
    ([B, T, R] efficacies, [B, T, R] addresses)."""
    return jax.vmap(unpack_events, in_axes=(0, None, None))(stream, T, R)


def truncate_stream(stream: EventStream, T: int,
                    step_budget: int) -> EventStream:
    """Drop records beyond the first ``step_budget`` of each timestep.

    Models a per-step link bandwidth: the kept records stay t-major and
    the stream stays drop-detectable — ``n_events`` is left at the TRUE
    count, so ``step_overflowed`` sees more true records than stored
    ones and reports the cut. Works on single ([E]) and batched
    ([B, E]) streams."""
    e = jnp.arange(stream.capacity, dtype=jnp.int32)
    seg = jnp.where(stream.valid, stream.t, T)

    def _counts(s):
        return jnp.zeros((T + 1,), jnp.int32).at[s].add(1, mode="drop")

    counts = _counts(seg) if seg.ndim == 1 else jax.vmap(_counts)(seg)
    offset = jnp.concatenate(
        [jnp.zeros((*counts.shape[:-1], 1), jnp.int32),
         jnp.cumsum(counts[..., :-1], axis=-1)], axis=-1)
    slot = e - jnp.take_along_axis(
        offset, jnp.clip(stream.t, 0, T), axis=-1)
    keep = stream.valid & (slot < step_budget)
    return stream._replace(eff=jnp.where(keep, stream.eff, 0.0),
                           valid=keep)


def window_stats(row_events_t) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(worst per-instance event count, worst per-instance-step count) of
    a [T, .., R] window — the quantities the density auto-switch gates on.
    Each instance of the prefix packs its own capacity-``max_events``
    stream, so the gate must hold for the worst instance."""
    fired = (row_events_t != 0.0).astype(jnp.int32)
    per_step = jnp.sum(fired, axis=-1)          # [T, ..]
    return jnp.max(jnp.sum(per_step, axis=0)), jnp.max(per_step)


def regroup_events(stream: EventStream, T: int, k_cap: int
                   ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Stream -> per-step [T, K] record grid (K = ``k_cap`` static).

    ``rows_tk/addr_tk/eff_tk``: slot k of step t holds that step's k-th
    event (row-ascending, the stream order); empty slots carry
    ``eff == 0`` so they contribute exactly nothing to the gathered
    reduction. Steps with more than ``k_cap`` events drop the tail —
    the same broken-promise regime as stream overflow, and gated by the
    same auto-switch fallback.
    """
    e = jnp.arange(stream.capacity, dtype=jnp.int32)
    seg = jnp.where(stream.valid, stream.t, T)
    counts = jnp.zeros((T + 1,), jnp.int32).at[seg].add(1)
    offset = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts[:-1])])
    slot = e - offset[jnp.clip(stream.t, 0, T)]
    dst = jnp.where(stream.valid & (slot < k_cap),
                    stream.t * k_cap + slot, T * k_cap)
    zi = jnp.zeros((T * k_cap,), jnp.int32)
    rows_tk = zi.at[dst].set(stream.row, mode="drop").reshape(T, k_cap)
    addr_tk = zi.at[dst].set(stream.addr, mode="drop").reshape(T, k_cap)
    eff_tk = jnp.zeros((T * k_cap,), jnp.float32).at[dst].set(
        stream.eff, mode="drop").reshape(T, k_cap)
    return rows_tk, addr_tk, eff_tk


def pack_regrouped(row_events_t, event_addr_t, max_events: int, k_cap: int
                   ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """[.., T, R] events -> the [.., T, K] record grid, with no stream.

    Bit-identical to ``regroup_events(pack_events(ev, ad, max_events), T,
    k_cap)`` on every input, overflow included: a fired row takes slot
    ``k``, its ordinal within the step, when the stream would have stored
    it (t-major index below ``max_events``) and regrouping would have kept
    it (``k < k_cap``). Each slot is then a sum over R of a one-hot
    select: at most one term is nonzero, so the sum is exact. No scatter:
    a TPU serializes scatter updates, and the stream form pays that on
    every one of the T * R slots. Any instance prefix.
    """
    T, R = row_events_t.shape[-2:]
    eff = row_events_t.astype(jnp.float32)
    fired = eff != 0.0
    # ordinals as 0/1 matmuls with f32 sums: exact on every backend, and
    # MXU work on a TPU in place of cumsum reduce-windows
    bits = fired.astype(jnp.bfloat16)
    ordinal = jnp.matmul(bits, jnp.triu(jnp.ones((R, R), jnp.bfloat16)),
                         preferred_element_type=jnp.float32) - 1.0
    first = jnp.sum(jnp.matmul(jnp.tril(jnp.ones((T, T), jnp.bfloat16), -1),
                               bits, preferred_element_type=jnp.float32),
                    axis=-1, keepdims=True)      # stream index of slot 0
    keep = fired & (first + ordinal < max_events) & (ordinal < k_cap)
    slot = jnp.where(keep, ordinal.astype(jnp.int32), k_cap)
    # [.., K, R, T]: the sum over R runs across sublanes with T on the
    # lanes, five times faster on a TPU v5e than R on the lanes
    hit = (jnp.swapaxes(slot, -1, -2)[..., None, :, :]
           == jnp.arange(k_cap, dtype=jnp.int32)[:, None, None])

    def gather(values_rt):
        picked = jnp.where(hit, values_rt[..., None, :, :], 0)
        return jnp.swapaxes(jnp.sum(picked, axis=-2), -1, -2)

    rows = jnp.broadcast_to(jnp.arange(R, dtype=jnp.int32)[:, None], (R, T))
    addr = jnp.swapaxes(event_addr_t.astype(jnp.int32), -1, -2)
    return gather(rows), gather(addr), gather(jnp.swapaxes(eff, -1, -2))


def default_max_events(T: int, R: int, threshold: float) -> int:
    """Stream capacity implied by a density threshold: the auto-switch
    takes the sparse path only while the window fits, so the capacity IS
    the density gate (rounded up to a lane-friendly multiple of 8)."""
    cap = int(math.ceil(threshold * T * R))
    return max(32, min(T * R, ((cap + 7) // 8) * 8))


def default_k_cap(R: int, threshold: float) -> int:
    """Per-step record capacity: sized for a Bernoulli(threshold) row
    census with generous Poisson headroom, so sub-threshold windows
    essentially never overflow a single step."""
    cap = int(math.ceil(4.0 * threshold * R)) + 4
    return max(8, min(R, ((cap + 3) // 4) * 4))
