"""Layer scopes and jit-safe telemetry counters for the emulation stack.

``LAYER_SCOPES`` declares the ``jax.named_scope`` each layer of the
emulator runs under, and ``scope`` enters one; they name ops in a
profile and add no instruction (see their comment below).

``Telemetry`` is a pytree of scalar counters (plus one fixed-size
histogram) threaded through the training scan as part of the carry. The
contract that makes it free when unused:

  * OFF is ``None``. Every update helper returns ``None`` for ``None``
    input without emitting a single op, so the disabled program is the
    *same jaxpr* as before telemetry existed — zero overhead, zero
    retrace risk, and trivially bit-identical outputs.
  * ON is read-only on the existing dataflow: counters are derived from
    values the emulation already computes (recorded spikes, the sparse
    gate's event census, the VM's returned register file, the rule's
    weight delta). No operand of the original math is touched, so
    spikes/weights/VM state are bit-identical with telemetry on — the
    invariant ``tests/test_obs.py`` asserts with ``assert_array_equal``
    across the fused/blocked/oracle/sparse backends.
  * Shapes are static. Counters are rank-0 ``int32``/``float32`` and the
    weight-update histogram has a fixed bin count, so the pytree carries
    through ``lax.scan`` unchanged regardless of network size, trial
    count, or instance prefix (counters are fleet-wide totals).

Counter catalogue (see README "Observability" for the full matrix):

  steps / trials           integrated dt steps, completed PPU trials
  in_events / out_spikes   nonzero driver events in, neuron spikes out
  rate_total               sum of rate counters at PPU read time
  dense_windows / sparse_windows
                           synaptic-window routing decisions (static
                           routes count too; one window call = one count)
  gated_windows            windows that went through the runtime
                           ``lax.cond`` census gate of ``sparse="auto"``
  overflow_fallbacks       auto-gated windows whose event census did NOT
                           fit the static stream capacities and fell back
                           to dense — the previously *silent* PR 6 path
  census_events_max / census_k_max
                           worst window event count / per-step count the
                           gate measured (capacity headroom indicator)
  routed_events / link_overflows / link_events_max
                           inter-chip events the wafer router placed on
                           the event bus (per-link-deduped records), the
                           number of link exchanges whose census exceeded
                           the per-link budget (compact mode: dropped
                           tails; auto mode: counted dense fallbacks —
                           either way never silent), and the worst
                           per-link event count seen (bus headroom
                           against the ~0.4M events/s budget)
  vm_runs / vm_sat_hits    PPU-VM program executions, and final register
                           lanes resting on the Q8.8 saturation rails
                           (0x7FFF / 0x8000 — fracsat clipping happened)
  dw_updates / dw_abs_max / dw_hist
                           weight-update count, largest |dw| (weight
                           LSBs), and a fixed-bin |dw| magnitude
                           histogram over all synapses and trials
  faults_injected          gauge: active fault SITES of the threaded
                           injection ``FaultPlan`` chain (stuck cells +
                           dead rows/neurons + CADC/store/link faults) —
                           any faulted run announces itself here
  faults_detected / blacklisted_rows
                           gauges: entries of the threaded *blacklist*
                           reduction plan (rows + neurons + links, and
                           the row count alone) — degradation is never
                           silent, same contract as the overflow paths
  link_reroutes            inter-chip events delivered through a
                           failover FORWARD rule (``WaferPlan`` reroute
                           around a dead link) instead of their original
                           route — counts the rerouted bus traffic
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Layer scopes
# ---------------------------------------------------------------------------

# The emulator's layers, as the names of the ``jax.named_scope``s that wrap
# them (scope name -> the scope it nests in, None at the top). A scope only
# writes the ``op_name`` metadata of the ops traced under it: a profile
# (``repro.obs.timing.profiler_trace``, viewed in TensorBoard or Perfetto)
# shows each op under its layer, and the compiled instructions are the same
# with the scopes as without them. The children of ``synaptic_phase`` split
# its work: the STP efficacy scan, the density gate's event census, and the
# sparse route (event packing, gather-matmul) or the dense route; inside the
# gate's ``lax.cond`` each route keeps its own child scope.
LAYER_SCOPES = {
    "synaptic_phase": None,
    "stp": "synaptic_phase",
    "census": "synaptic_phase",
    "pack_events": "synaptic_phase",
    "gather_matmul": "synaptic_phase",
    "dense_matmul": "synaptic_phase",
    "neuron_window": None,
    "correlation_sensors": None,
    "ppu_rule": None,
    "event_generation": None,
    "inter_chip_router": None,
}


def scope(name: str):
    """``jax.named_scope(name)`` for a declared layer scope; an undeclared
    name raises, so a profile never shows a layer the table lacks."""
    if name not in LAYER_SCOPES:
        raise ValueError(f"undeclared layer scope {name!r}; declared: "
                         f"{sorted(LAYER_SCOPES)}")
    return jax.named_scope(name)


# |dw| histogram bin edges in weight LSBs: bin 0 is "below one Q8.8 LSB"
# (effectively unchanged), the rest are log2-spaced up to the ±45 clip
# range of the §5 signed weights. searchsorted(E, x) -> bin index.
DW_EDGES = np.asarray([1.0 / 256, 1.0 / 64, 1.0 / 16, 0.25, 0.5,
                       1.0, 2.0, 4.0, 8.0, 16.0, 32.0], np.float32)
DW_BINS = len(DW_EDGES) + 1

_I32_FIELDS = ("steps", "trials", "in_events", "out_spikes",
               "dense_windows", "sparse_windows", "gated_windows",
               "overflow_fallbacks", "census_events_max", "census_k_max",
               "routed_events", "link_overflows", "link_events_max",
               "vm_runs", "vm_sat_hits", "dw_updates",
               "faults_injected", "faults_detected", "blacklisted_rows",
               "link_reroutes")


class Telemetry(NamedTuple):
    steps: jnp.ndarray               # [] i32 integrated dt steps
    trials: jnp.ndarray              # [] i32 completed trials
    in_events: jnp.ndarray           # [] i32 nonzero input row events
    out_spikes: jnp.ndarray          # [] i32 output spikes
    rate_total: jnp.ndarray          # [] f32 rate counters at PPU reads
    dense_windows: jnp.ndarray       # [] i32 windows routed dense
    sparse_windows: jnp.ndarray      # [] i32 windows routed sparse
    gated_windows: jnp.ndarray       # [] i32 runtime census-gated windows
    overflow_fallbacks: jnp.ndarray  # [] i32 census overflow -> dense
    census_events_max: jnp.ndarray   # [] i32 worst gated window events
    census_k_max: jnp.ndarray        # [] i32 worst gated per-step events
    routed_events: jnp.ndarray       # [] i32 inter-chip events routed
    link_overflows: jnp.ndarray      # [] i32 link censuses over budget
    link_events_max: jnp.ndarray     # [] i32 worst per-link event count
    vm_runs: jnp.ndarray             # [] i32 PPU-VM program executions
    vm_sat_hits: jnp.ndarray         # [] i32 register lanes on the rails
    dw_updates: jnp.ndarray          # [] i32 weight-update applications
    faults_injected: jnp.ndarray     # [] i32 gauge: injected fault sites
    faults_detected: jnp.ndarray     # [] i32 gauge: blacklist entries
    blacklisted_rows: jnp.ndarray    # [] i32 gauge: blacklisted rows
    link_reroutes: jnp.ndarray       # [] i32 events on failover forwards
    dw_abs_max: jnp.ndarray          # [] f32 largest |dw| seen (LSBs)
    dw_hist: jnp.ndarray             # [DW_BINS] i32 |dw| histogram


def init_telemetry() -> Telemetry:
    # one DISTINCT zero buffer per field: training donates the scan carry,
    # and donation rejects the same buffer appearing twice in it
    return Telemetry(
        **{f: jnp.array(0, jnp.int32) for f in _I32_FIELDS},
        rate_total=jnp.array(0.0, jnp.float32),
        dw_abs_max=jnp.array(0.0, jnp.float32),
        dw_hist=jnp.zeros((DW_BINS,), jnp.int32))


# ---------------------------------------------------------------------------
# Update helpers — every one is the identity on None (telemetry OFF)
# ---------------------------------------------------------------------------

def count_run(tele: Optional[Telemetry], row_spikes_t, out_spikes_t
              ) -> Optional[Telemetry]:
    """One integrated window: dt steps, input events, output spikes.

    Reads the window's *recorded* inputs/outputs (outside the dt scan),
    so the emulation loop itself is untouched. Totals sum over any
    instance prefix.
    """
    if tele is None:
        return None
    T = row_spikes_t.shape[0]
    return tele._replace(
        steps=tele.steps + jnp.int32(T),
        in_events=tele.in_events
        + jnp.count_nonzero(row_spikes_t).astype(jnp.int32),
        out_spikes=tele.out_spikes
        + jnp.sum(out_spikes_t).astype(jnp.int32))


def count_route(tele: Optional[Telemetry], sparse: bool
                ) -> Optional[Telemetry]:
    """A *statically* routed synaptic window (no runtime gate): the
    ``sparse="never"``/work-floor dense program or forced ``"always"``."""
    if tele is None:
        return None
    if sparse:
        return tele._replace(sparse_windows=tele.sparse_windows + 1)
    return tele._replace(dense_windows=tele.dense_windows + 1)


def count_gate(tele: Optional[Telemetry], fits, n_events, k_max
               ) -> Optional[Telemetry]:
    """One ``sparse="auto"`` census-gate decision: ``fits`` routed sparse,
    ``~fits`` is a capacity-overflow fallback to dense (the event stream
    would have dropped records — PR 6 took this branch silently)."""
    if tele is None:
        return None
    took = fits.astype(jnp.int32)
    return tele._replace(
        gated_windows=tele.gated_windows + 1,
        sparse_windows=tele.sparse_windows + took,
        dense_windows=tele.dense_windows + (1 - took),
        overflow_fallbacks=tele.overflow_fallbacks + (1 - took),
        census_events_max=jnp.maximum(tele.census_events_max,
                                      n_events.astype(jnp.int32)),
        census_k_max=jnp.maximum(tele.census_k_max,
                                 k_max.astype(jnp.int32)))


def count_links(tele: Optional[Telemetry], n_link, fits_link
                ) -> Optional[Telemetry]:
    """One inter-chip routing exchange: ``n_link`` is the per-link event
    census ([L] i32, records after per-link dedup — the counts the bus
    would carry), ``fits_link`` the per-link budget verdict ([L] bool from
    ``events.census_fits``). A link over budget is an overflow: the
    compact transport DROPPED its tail, the auto transport fell back to
    the dense exchange — both land in ``link_overflows``, so the PR 6
    silent-drop regime cannot recur on the wafer bus."""
    if tele is None:
        return None
    n_link = n_link.astype(jnp.int32)
    return tele._replace(
        routed_events=tele.routed_events + jnp.sum(n_link),
        link_overflows=tele.link_overflows
        + jnp.count_nonzero(~fits_link).astype(jnp.int32),
        link_events_max=jnp.maximum(tele.link_events_max,
                                    jnp.max(n_link)))


def count_trial(tele: Optional[Telemetry], rate_counters
                ) -> Optional[Telemetry]:
    """One completed trial; ``rate_counters`` as read by the PPU (before
    the post-read reset)."""
    if tele is None:
        return None
    return tele._replace(
        trials=tele.trials + 1,
        rate_total=tele.rate_total
        + jnp.sum(rate_counters).astype(jnp.float32))


def count_vm(tele: Optional[Telemetry], regs) -> Optional[Telemetry]:
    """One PPU-VM program execution: count final register lanes resting
    on the Q8.8 fracsat rails (0x7FFF / 0x8000) — evidence that the
    saturating arithmetic clipped. Reads the register file the executor
    already returns, so every executor (numpy/scan/specialized/pallas)
    reports identically."""
    if tele is None:
        return None
    from repro.ppuvm import isa
    on_rail = (regs == isa.I16MAX) | (regs == isa.I16MIN)
    return tele._replace(
        vm_runs=tele.vm_runs + 1,
        vm_sat_hits=tele.vm_sat_hits
        + jnp.count_nonzero(on_rail).astype(jnp.int32))


def count_dw(tele: Optional[Telemetry], w_old, w_new
             ) -> Optional[Telemetry]:
    """One weight update: |dw| magnitude histogram over all synapses
    (weight-LSB units; bin edges ``DW_EDGES``)."""
    if tele is None:
        return None
    dw = jnp.abs(jnp.asarray(w_new, jnp.float32)
                 - jnp.asarray(w_old, jnp.float32)).reshape(-1)
    # bin b holds the |dw| with exactly b edges below them (the bins of
    # ``searchsorted``), counted as differences of "above edge k" counts:
    # fused reductions, where a scatter-add of every synapse into the bins
    # serializes on a TPU (on a v5e the scatter cost about 590 us per
    # emulated chip-trial, all the counters together 4-15 us without it)
    above = jnp.sum(dw[None, :] > jnp.asarray(DW_EDGES)[:, None], axis=1,
                    dtype=jnp.int32)
    ge = jnp.concatenate([jnp.full((1,), dw.size, jnp.int32), above,
                          jnp.zeros((1,), jnp.int32)])
    return tele._replace(
        dw_updates=tele.dw_updates + 1,
        dw_abs_max=jnp.maximum(tele.dw_abs_max, jnp.max(dw)),
        dw_hist=tele.dw_hist + ge[:-1] - ge[1:])


def count_faults(tele: Optional[Telemetry], faults) -> Optional[Telemetry]:
    """Announce the threaded fault overlays (``repro.faults``): gauges set
    by ``maximum`` so every hook site (AnnCore window, router exchange,
    VM store) reports the same totals without double counting. Injection
    plans land in ``faults_injected`` (their active site count), blacklist
    reduction plans in ``faults_detected``/``blacklisted_rows``. All
    counts are host constants of the plan — identity on ``None`` faults
    AND on ``None`` telemetry, so the off path stays the same jaxpr."""
    if tele is None or faults is None:
        return None if tele is None else tele
    from repro.faults.model import as_plans
    inj = det = rows = 0
    for p in as_plans(faults):
        if p.is_blacklist:
            det += p.total_sites
            rows += p.n_dead_rows
        else:
            inj += p.total_sites
    if inj:
        tele = tele._replace(faults_injected=jnp.maximum(
            tele.faults_injected, jnp.int32(inj)))
    if det:
        tele = tele._replace(
            faults_detected=jnp.maximum(tele.faults_detected,
                                        jnp.int32(det)),
            blacklisted_rows=jnp.maximum(tele.blacklisted_rows,
                                         jnp.int32(rows)))
    return tele


def count_reroutes(tele: Optional[Telemetry], n_fwd) -> Optional[Telemetry]:
    """One routing exchange's failover traffic: ``n_fwd`` is the event
    census of the forward-rule delivery grids (events a ``WaferPlan``
    reroute carried around a dead link). Identity on ``None`` telemetry
    or when the plan has no forward rules (``n_fwd is None``)."""
    if tele is None or n_fwd is None:
        return tele
    return tele._replace(
        link_reroutes=tele.link_reroutes + n_fwd.astype(jnp.int32))


# Counters that hold a maximum or a gauge, and counters of windows (every
# device of a sharded wafer runs every window); the rest are totals over
# the chips.
_MAX_FIELDS = ("census_events_max", "census_k_max", "link_events_max",
               "faults_injected", "faults_detected", "blacklisted_rows",
               "dw_abs_max")
_WINDOW_FIELDS = ("steps", "dense_windows", "sparse_windows",
                  "gated_windows", "overflow_fallbacks")


def fold_devices(tele: Optional[Telemetry], parts: Telemetry
                 ) -> Optional[Telemetry]:
    """Fold the counters each device of a sharded window counted from zero
    (``parts``: every leaf with a leading device axis) into the fleet-wide
    ``tele``: totals over the chips add up, maxima and gauges take the
    largest, and counts of windows and steps take the largest device's
    count. Each device gates its own chips' census, so the route counts
    equal those of the unsharded program whenever the devices' gates
    agree."""
    if tele is None:
        return None
    out = {}
    for f, old in tele._asdict().items():
        new = getattr(parts, f)
        if f in _MAX_FIELDS:
            out[f] = jnp.maximum(old, jnp.max(new, axis=0))
        elif f in _WINDOW_FIELDS:
            out[f] = old + jnp.max(new, axis=0)
        else:
            out[f] = old + jnp.sum(new, axis=0)
    return Telemetry(**out)


# ---------------------------------------------------------------------------
# Host-side summary
# ---------------------------------------------------------------------------

def summary(tele: Optional[Telemetry]) -> Optional[dict]:
    """Pull the counters to the host as plain Python numbers (the form
    the run report embeds). Pure host-side read — emitting (or not
    emitting) a report never touches the compiled program, which is what
    the zero-retrace test pins down."""
    if tele is None:
        return None
    d = {}
    for k, v in tele._asdict().items():
        a = np.asarray(v)
        d[k] = a.tolist() if a.ndim else a.item()
    d["dw_hist_edges"] = DW_EDGES.tolist()
    return d
