"""The assembled analog network core (anncore).

One object holds the full machine state (neurons, synapses, STP, correlation
sensors) and ``run`` integrates it over a time window with ``lax.scan`` —
the accelerated-time emulation. Everything broadcasts over a leading
instance dim, so a *batch of independent chips* (virtual instances for MC
calibration, or parallel experiment seeds) runs as one vectorized program —
that is how the machine model maps onto the TPU mesh (instances over
``data``, synapse columns over ``model``).

Backends
--------
``run`` has two implementations, selected by the ``backend`` constructor
argument (auto-selected like ``repro.kernels/*/ops.py`` selects its impl):

``oracle``
    The literal per-dt scan of ``step``: every timestep recomputes the
    address-match mask, materializes two [.., R, C] correlation
    accumulators, and strided-slices the Dale rows. Ground truth for
    equivalence tests and the host-style baseline.

``fused`` (the ``auto`` default on CPU)
    The hot path. Exploits two structural facts of the machine:
    (1) STP efficacy depends only on the *input* events, so the whole
    efficacy trajectory is precomputed by a cheap [.., R]-wide scan;
    (2) weights/addresses are constant between PPU writes, so the per-step
    masked matmul becomes ONE time-batched event x weight matmul (Dale
    exc/inh rows pre-split once at window entry) routed through the
    ``synray`` Pallas kernel on TPU. The remaining dt scan touches only
    [.., C] neuron state, and the correlation-sensor update — which never
    feeds back into neuron dynamics within a trial — is hoisted out of the
    scan entirely and applied once per window by the fused
    ``correlation_window`` kernel (T x fewer HBM round trips).

``blocked`` (the ``auto`` default on TPU)
    ``fused`` with the last per-dt scan replaced by the time-blocked
    neuron window (``repro.kernels.neuron_scan``): the neuron state
    integrates a whole time block per step — VMEM-resident in the Pallas
    kernel on TPU (no XLA while loop over dts at all, instances on the
    kernel grid), a packed-carry scan over blocks on CPU. Bit-identical
    spikes/records to the oracle: the per-step op trees are shared
    (``adex.integrate_currents``/``membrane_step``), only their schedule
    changes. ``block_size`` tunes the CPU block (default 8, measured on
    the CPU container); ``kernel_block`` the TPU kernel's time block.

``kernel_impl`` forwards to the kernel wrappers: ``auto`` (pallas on TPU,
jnp oracle elsewhere), ``pallas``, ``interpret``, or ``ref``.
"""
from __future__ import annotations

import copy
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.bss2 import BSS2Config
from repro.core import adex, correlation, stp, synapse
from repro.faults import inject as finject
from repro.obs import trace as obs_trace


class AnnCoreState(NamedTuple):
    neuron: adex.NeuronState
    stp: stp.STPState
    corr: correlation.CorrelationState
    syn: synapse.SynapseArray
    rate_counters: jnp.ndarray    # [..., C] spike counts since last PPU read


class AnnCore:
    """Stateless integrator bound to a config + a virtual instance.

    ``inst`` carries the mismatch realisation (see repro.verif.mismatch):
      neuron_params: dict of [..., C] arrays
      weight_gain:   [..., C]   synaptic DAC gain spread
      stp_offset:    [..., R]   driver efficacy offset (Fig. 4)
      stp_calib:     [..., R]   4-bit trim codes
      cadc_offset/cadc_gain: [..., C]

    ``backend``: "auto" | "oracle" | "fused" | "blocked" (see module
    docstring; "auto" resolves to "blocked" on TPU — the whole-trial
    on-chip path — and "fused" elsewhere).
    ``kernel_impl``: impl forwarded to the Pallas kernel wrappers.
    ``const_addr``: promise that within any one ``run`` window the event
    address on each row never changes (each driver row carries a single
    source, as in the §5 experiment wiring). Lets the fused CPU path
    resolve the address-match mask once per window into an effective
    weight matrix instead of re-deriving it per step (and the CPU gate's
    default sizing, ``synapse.default_sparse_threshold``); on the TPU
    nothing reads it. A row that receives
    routed events breaks it (the §5 wafer's relay rows carry address 0
    from the inputs and 63 from the bus), so ``hybrid.make_experiment``
    makes the promise only for plans without relay rows.
    ``block_size``/``trace_block``/``kernel_block``: time-block sizes of
    the "blocked" backend (membrane scan slab, current-trace slab, and
    the Pallas kernel's VMEM-resident block).
    ``sparse_mode``: the event-sparse synaptic path of the fused/blocked
    backends — "auto" (default: route windows through the sparse
    gather-accumulate kernel when they provably fit the event capacities,
    dense otherwise — bit-identical either way), "never", or "always"
    (see ``synapse.synaptic_current_window``). ``sparse_threshold`` /
    ``sparse_max_events`` / ``sparse_k_cap`` override the density gate
    and the static stream capacities.
    ``telemetry``: when True, ``run`` threads a jit-safe
    ``repro.obs.trace.Telemetry`` counter pytree (auto-initialized per
    call unless the caller passes one) and returns it under
    ``outputs["telemetry"]`` — spike/event totals plus the synaptic
    routing decisions. Off (the default) compiles to the exact
    pre-telemetry program; on/off outputs are bit-identical.
    ``faults``: a ``repro.faults`` overlay (``None`` | ``FaultPlan`` |
    tuple of plans, injection first, blacklist reduction last) applied
    at the hook sites documented in ``repro.faults.inject``. ``None``
    is the identity on every hook — the same-jaxpr off-path contract —
    and a given overlay produces bit-identical outputs on every backend
    (the hooks sit on backend-shared dataflow).
    """

    def __init__(self, cfg: BSS2Config, inst: Dict, backend: str = "auto",
                 kernel_impl: str = "auto", const_addr: bool = False,
                 block_size: int = 8, trace_block: int = 8,
                 kernel_block: int = 32, sparse_mode: str = "auto",
                 sparse_threshold: float = None,
                 sparse_max_events: int = None, sparse_k_cap: int = None,
                 telemetry: bool = False, faults=None):
        self.cfg = cfg
        self.inst = inst
        if backend == "auto":
            backend = ("blocked" if jax.default_backend() == "tpu"
                       else "fused")
        if backend not in ("oracle", "fused", "blocked"):
            raise ValueError(f"unknown backend {backend!r}")
        self.backend = backend
        self.kernel_impl = kernel_impl
        self.const_addr = const_addr
        self.block_size = block_size
        self.trace_block = trace_block
        self.kernel_block = kernel_block
        self.sparse_mode = sparse_mode
        self.sparse_threshold = sparse_threshold
        self.sparse_max_events = sparse_max_events
        self.sparse_k_cap = sparse_k_cap
        self.telemetry = telemetry
        self.faults = faults
        # membrane decay factors handed in by ``_run_per_device``; None
        # derives them from ``inst`` where they are used
        self._decays = None

    def init_state(self, prefix=()) -> AnnCoreState:
        cfg = self.cfg
        r, c = cfg.n_rows, cfg.n_cols
        return AnnCoreState(
            neuron=adex.init_state((*prefix, c), self.inst["neuron_params"]),
            stp=stp.init_state((*prefix, r)),
            corr=correlation.init_state(prefix, r, c),
            syn=synapse.init_array(prefix, r, c),
            rate_counters=jnp.zeros((*prefix, c), jnp.float32),
        )

    def step(self, state: AnnCoreState, row_spikes, row_addr, ext_current=0.0):
        """One dt of the full core (the oracle semantics).

        row_spikes: [..., R] float {0,1} events entering the drivers;
        row_addr:   [..., R] int8 event addresses;
        """
        cfg = self.cfg
        dt = cfg.dt
        row_spikes = finject.rows(self.faults, row_spikes)
        eff = stp.efficacy(state.stp, row_spikes, u=cfg.stp_u,
                           offset=self.inst["stp_offset"],
                           calib_code=self.inst["stp_calib"])
        new_stp = stp.update(state.stp, row_spikes, u=cfg.stp_u,
                             tau_rec=cfg.stp_tau_rec, dt=dt)

        # signed rows: even rows excitatory, odd rows inhibitory (Dale);
        # stuck SRAM cells override the stored weight at the analog read
        w_read = finject.weights(self.faults, state.syn.weights)
        i_cols_exc = synapse.synaptic_current(
            w_read[..., 0::2, :], state.syn.addresses[..., 0::2, :],
            eff[..., 0::2], row_addr[..., 0::2], self.inst["weight_gain"])
        i_cols_inh = synapse.synaptic_current(
            w_read[..., 1::2, :], state.syn.addresses[..., 1::2, :],
            eff[..., 1::2], row_addr[..., 1::2], self.inst["weight_gain"])

        new_neuron, out_spikes = adex.step(
            state.neuron, i_cols_exc * 60.0 + ext_current, i_cols_inh * 60.0,
            self.inst["neuron_params"], dt, adex=cfg.neuron.adex)
        # output-driver faults: hot forces 1, dead forces 0 — BEFORE the
        # sensors and counters; the membrane keeps integrating unmasked
        out_spikes = finject.spikes(self.faults, out_spikes)

        # sensor time constants ~ tau_syn: long traces let consecutive
        # pattern bursts sample each other's post-activity and flip the
        # eligibility sign (measured: elig[A->even] < 0 on A-trials with
        # 4x tau — see EXPERIMENTS.md, R-STDP bring-up log)
        new_corr = correlation.update(
            state.corr, row_spikes, out_spikes,
            tau_pre=cfg.neuron.tau_syn_exc,
            tau_post=cfg.neuron.tau_syn_exc, dt=dt)

        new_state = AnnCoreState(
            neuron=new_neuron, stp=new_stp, corr=new_corr, syn=state.syn,
            rate_counters=state.rate_counters + out_spikes)
        return new_state, out_spikes

    def run(self, state: AnnCoreState, row_spikes_t, row_addr_t,
            record_v: bool = False, unroll: Optional[int] = None,
            telemetry=None):
        """Integrate a [T, ..., R] event stream. Returns (state, outputs).

        outputs: dict(spikes=[T, ..., C], v=[T, ..., C] if record_v,
                      telemetry=Telemetry if threading telemetry)

        ``unroll=None`` picks the backend default: 1 for the oracle (the
        literal reference), 4 for the fused path (its dt-scan body is
        [.., C]-tiny, so moderate unrolling amortizes loop overhead;
        measured best on the CPU container, larger factors only grow the
        compiled loop body past cache).

        ``telemetry``: pass a ``Telemetry`` pytree to accumulate into it
        (the training scan threads it through the carry); ``None``
        auto-initializes a fresh one iff the core was built with
        ``telemetry=True``, else telemetry is off and the emitted program
        is identical to the pre-telemetry one.

        Args:
          state: ``AnnCoreState`` carry (membranes, STP, correlation
            accumulators, synapse array).
          row_spikes_t: [T, ..., R] float driver events (0/1 before STP).
          row_addr_t: [T, ..., R] int8 event addresses.
          record_v: also return the membrane trace (costs memory).
          unroll: dt-scan unroll override (``None`` = backend default).
          telemetry: ``Telemetry`` pytree, or ``None`` (see above).

        Returns:
          ``(state, outputs)`` — outputs as documented above.

        Contract pointers: the three backends are bit-identical
        (tests/test_blocked.py), the dense/sparse synaptic routes are
        bit-identical (tests/test_sparse.py), telemetry on/off is
        bit-identical and off is the same jaxpr (tests/test_obs.py),
        fault injection is backend-invariant (tests/test_faults.py).
        """
        if telemetry is None and self.telemetry:
            telemetry = obs_trace.init_telemetry()
        # dead drivers zero their events before EVERY phase (STP, synaptic
        # matmul, correlation pre-traces, telemetry census) — one shared
        # hook site covers all backends; re-application inside the oracle
        # ``step`` is an exact no-op (masking is idempotent)
        row_spikes_t = finject.rows(self.faults, row_spikes_t)
        telemetry = obs_trace.count_faults(telemetry, self.faults)
        if self.backend == "oracle":
            return self._run_oracle(state, row_spikes_t, row_addr_t,
                                    record_v=record_v, unroll=unroll or 1,
                                    telemetry=telemetry)
        return self._run_windowed(state, row_spikes_t, row_addr_t,
                                  record_v=record_v, unroll=unroll or 4,
                                  telemetry=telemetry)

    def run_routed(self, state: AnnCoreState, routed_ev, row_spikes_t,
                   row_addr_t, router, record_v: bool = False,
                   unroll: Optional[int] = None, telemetry=None):
        """One window with the inter-chip router closed around it.

        ``routed_ev`` is the [T, K, R] delivery grid the *previous*
        window's spikes deposited (``repro.wafer.router``): it merges
        into this window's external inputs before integration, and this
        window's output spikes are routed into ``outputs["routed"]`` for
        the next window — the one-window bus-latency budget. With
        telemetry threading, the router's link census lands in the same
        ``outputs["telemetry"]`` pytree as the emulation counters.

        Args:
          state: per-chip ``AnnCoreState`` (instance prefix ``(K,)``).
          routed_ev: [T, K, R] delivery grid from the previous window
            (``router.empty_grid(T)`` for the first).
          row_spikes_t / row_addr_t: [T, K, R] external events as in
            ``run``.
          router: an ``repro.wafer.InterChipRouter``.
          record_v / unroll / telemetry: as in ``run``.

        Returns:
          ``(state, outputs)`` with ``outputs["routed"]`` the next
          window's delivery grid.

        Contract pointers: split == monolithic and transport
        interchangeability live in tests/test_wafer.py; the mapper's
        cross-K round trip (tests/test_mapper.py::TestExactness) runs
        through this entry point via ``repro.wafer.router.run_windows``.
        """
        if telemetry is None and self.telemetry:
            telemetry = obs_trace.init_telemetry()
        with obs_trace.scope("inter_chip_router"):
            ev, ad = router.merge(routed_ev, row_spikes_t, row_addr_t)
        if router._axis is not None and self._pallas_kernels():
            state, out = self._run_per_device(router, state, ev, ad,
                                              record_v, unroll, telemetry)
        else:
            state, out = self.run(state, ev, ad, record_v=record_v,
                                  unroll=unroll, telemetry=telemetry)
        with obs_trace.scope("inter_chip_router"):
            routed, tele = router.route(out["spikes"],
                                        out.get("telemetry", telemetry),
                                        routed_in=routed_ev)
        out["routed"] = routed
        if tele is not None:
            out["telemetry"] = tele
        return state, out

    def _pallas_kernels(self) -> bool:
        """True when ``run`` calls the Pallas kernels (native on TPU, or
        interpreted: the CPU rehearsal of the same program)."""
        impl = self.kernel_impl
        if impl == "auto":
            impl = "pallas" if jax.default_backend() == "tpu" else "ref"
        return self.backend != "oracle" and impl in ("pallas", "interpret")

    def _run_per_device(self, router, state, ev, ad, record_v, unroll,
                        telemetry):
        """``run`` under ``shard_map`` over the router's chip axis: each
        device integrates only its own chips. The TPU compiler refuses to
        partition a Pallas kernel across devices ("Mosaic kernels cannot
        be automatically partitioned"), so a sharded wafer with Pallas
        kernels needs this per-device program; chips are
        independent within a window, so it computes what ``run`` does.
        Telemetry: each device counts its own chips from zero, and
        ``obs.trace.fold_devices`` folds the devices' counters into the
        fleet-wide pytree. Fault overlays are fleet-wide and are not split
        per device here.

        The membrane decay factors are computed outside the ``shard_map``.
        On the local path the instance is a constant and XLA folds them on
        the host; inside, each device would compute them from its slice,
        and a TPU's exp and division round differently from the host's,
        enough to flip spikes within a few windows."""
        from jax.sharding import PartitionSpec as P
        if self.faults is not None:
            raise NotImplementedError(
                "a sharded wafer with native kernels runs without fault "
                "overlays")
        axis, K = router._axis, router.K
        chips = lambda tree: jax.tree.map(        # noqa: E731
            lambda x: P(axis) if x.ndim and x.shape[0] == K else P(), tree)
        out_spec = dict(spikes=P(None, axis))
        if record_v:
            out_spec["v"] = P(None, axis)
        if telemetry is not None:
            out_spec["telemetry"] = jax.tree.map(lambda _: P(axis),
                                                 telemetry)
        decays = adex.decay_factors(self.inst["neuron_params"], self.cfg.dt)

        def body(inst, dec, st, e, a):
            local = copy.copy(self)
            local.inst = inst
            local._decays = dec
            local.telemetry = telemetry is not None
            st, out = local.run(st, e, a, record_v=record_v, unroll=unroll)
            if telemetry is not None:
                # a leading device axis, for the fold below
                out["telemetry"] = jax.tree.map(lambda x: x[None],
                                                out["telemetry"])
            return st, out

        state, out = jax.shard_map(
            body, mesh=router._mesh,
            in_specs=(chips(self.inst), chips(decays), chips(state),
                      P(None, axis), P(None, axis)),
            out_specs=(chips(state), out_spec), check_vma=False)(
                self.inst, decays, state, ev, ad)
        if telemetry is not None:
            out["telemetry"] = obs_trace.fold_devices(telemetry,
                                                      out["telemetry"])
        return state, out

    def _run_oracle(self, state: AnnCoreState, row_spikes_t, row_addr_t,
                    record_v: bool = False, unroll: int = 1,
                    telemetry=None):

        def body(s, xs):
            sp, ad = xs
            s2, out = self.step(s, sp, ad)
            rec = (out, s2.neuron.v) if record_v else (out,)
            return s2, rec

        state, recs = jax.lax.scan(body, state, (row_spikes_t, row_addr_t),
                                   unroll=unroll)
        out = dict(spikes=recs[0])
        if record_v:
            out["v"] = recs[1]
        if telemetry is not None:
            # the oracle routes every step through the per-dt dense matmul
            out["telemetry"] = obs_trace.count_run(
                telemetry, row_spikes_t, recs[0])
        return state, out

    def _window_currents(self, state: AnnCoreState, row_spikes_t,
                         row_addr_t, unroll: int, telemetry=None):
        """Phases 1+2 shared by the fused and blocked backends: the STP
        efficacy trajectory (a cheap [.., R]-wide scan) and the whole
        window's synaptic currents as ONE time-batched event x weight
        matmul with the Dale rows pre-split."""
        cfg = self.cfg
        dt = cfg.dt
        inst = self.inst

        # 1. STP efficacy trajectory: depends only on the input events, so
        #    the whole [T, .., R] trajectory comes out of a cheap scan that
        #    never touches the [.., R, C] synapse array. The calibrated
        #    mismatch scale and the recovery increment are loop-invariant
        #    (bit-exact hoists — same op trees).
        with obs_trace.scope("stp"):
            scale = stp.efficacy_scale(inst["stp_offset"],
                                       inst["stp_calib"])
            recovery = stp.recovery_factor(cfg.stp_tau_rec, dt)

            def stp_body(s, sp):
                eff = stp.efficacy(s, sp, u=cfg.stp_u, scale=scale)
                return (stp.update(s, sp, u=cfg.stp_u, recovery=recovery),
                        eff)

            new_stp, eff_t = jax.lax.scan(stp_body, state.stp,
                                          row_spikes_t, unroll=unroll)

        # 2. Dale rows pre-split once per window; synaptic currents for ALL
        #    timesteps in one event x weight matmul (time = batch axis of
        #    the synray kernel).
        syn = state.syn
        gain = inst["weight_gain"]
        w_read = finject.weights(self.faults, syn.weights)
        sparse_kw = dict(sparse=self.sparse_mode,
                         sparse_threshold=self.sparse_threshold,
                         max_events=self.sparse_max_events,
                         k_cap=self.sparse_k_cap)
        i_exc_t = synapse.synaptic_current_window(
            w_read[..., 0::2, :], syn.addresses[..., 0::2, :],
            eff_t[..., 0::2], row_addr_t[..., 0::2], gain,
            impl=self.kernel_impl, const_addr=self.const_addr,
            telemetry=telemetry, **sparse_kw)
        if telemetry is not None:
            i_exc_t, telemetry = i_exc_t
        i_inh_t = synapse.synaptic_current_window(
            w_read[..., 1::2, :], syn.addresses[..., 1::2, :],
            eff_t[..., 1::2], row_addr_t[..., 1::2], gain,
            impl=self.kernel_impl, const_addr=self.const_addr,
            telemetry=telemetry, **sparse_kw)
        if telemetry is not None:
            i_inh_t, telemetry = i_inh_t
        # current scaling vectorized over the whole window, not per step
        return new_stp, i_exc_t * 60.0, i_inh_t * 60.0, telemetry

    def _neuron_window(self, neuron, rate_counters, i_exc_t, i_inh_t,
                       record_v: bool, unroll: int):
        """Phase 3: membrane integration over the pre-fused currents —
        the neuron-only dt scan (fused) or the time-blocked window
        (blocked: a whole block per step, VMEM-resident in the Pallas
        kernel, packed-carry block scan on CPU). Returns
        ``(new_neuron, rate_counters, recs)``."""
        cfg = self.cfg
        decays = self._decays
        if decays is None:
            decays = adex.decay_factors(self.inst["neuron_params"], cfg.dt)
        if self.backend == "blocked":
            from repro.kernels.neuron_scan import ops as neuron_ops
            return neuron_ops.neuron_window(
                neuron, rate_counters, i_exc_t, i_inh_t,
                self.inst["neuron_params"], dt=cfg.dt,
                use_adex=cfg.neuron.adex, decays=decays,
                impl=self.kernel_impl, block=self.block_size,
                trace_block=self.trace_block,
                kernel_block=self.kernel_block, record_v=record_v)

        # fused: O(C) per step with the time-invariant decay factors
        # hoisted out of the loop
        dt, inst = cfg.dt, self.inst

        def body(carry, xs):
            n, rc = carry
            ie, ii = xs
            n2, out = adex.step(n, ie, ii, inst["neuron_params"], dt,
                                adex=cfg.neuron.adex, decays=decays)
            rec = (out, n2.v) if record_v else (out,)
            return (n2, rc + out), rec

        (new_neuron, rate_counters), recs = jax.lax.scan(
            body, (neuron, rate_counters), (i_exc_t, i_inh_t),
            unroll=unroll)
        return new_neuron, rate_counters, recs

    def _run_windowed(self, state: AnnCoreState, row_spikes_t, row_addr_t,
                      record_v: bool = False, unroll: int = 1,
                      telemetry=None):
        """The fused/blocked pipeline: window currents (phases 1+2) ->
        neuron window (phase 3) -> hoisted correlation window (phase 4:
        sensors never feed back into the dynamics within a window, so one
        fused kernel call replays the whole T-window per VMEM tile).
        Each phase runs under its layer's scope
        (``obs.trace.LAYER_SCOPES``)."""
        cfg = self.cfg
        with obs_trace.scope("synaptic_phase"):
            new_stp, i_exc_t, i_inh_t, telemetry = self._window_currents(
                state, row_spikes_t, row_addr_t, unroll, telemetry)
        with obs_trace.scope("neuron_window"):
            new_neuron, rate_counters, recs = self._neuron_window(
                state.neuron, state.rate_counters, i_exc_t, i_inh_t,
                record_v, unroll)
        out_spikes_t = recs[0]
        if self.faults is not None:
            out_spikes_t = finject.spikes(self.faults, out_spikes_t)
            rate_counters = finject.rates(self.faults, rate_counters,
                                          state.rate_counters,
                                          row_spikes_t.shape[0])
        with obs_trace.scope("correlation_sensors"):
            new_corr = correlation.window(
                state.corr, row_spikes_t, out_spikes_t,
                tau_pre=cfg.neuron.tau_syn_exc,
                tau_post=cfg.neuron.tau_syn_exc, dt=cfg.dt,
                impl=self.kernel_impl)
        new_state = AnnCoreState(neuron=new_neuron, stp=new_stp,
                                 corr=new_corr, syn=state.syn,
                                 rate_counters=rate_counters)
        out = dict(spikes=out_spikes_t)
        if record_v:
            out["v"] = recs[1]
        if telemetry is not None:
            out["telemetry"] = obs_trace.count_run(
                telemetry, row_spikes_t, out_spikes_t)
        return new_state, out
