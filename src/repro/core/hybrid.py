"""Hybrid plasticity: the fused on-device experiment step (paper §2.2, §5).

The defining property of BrainScaleS-2 is that the learning rule runs *on*
the accelerator: the PPU reads rate counters and correlation sensors, joins
them with the reward, and writes 6-bit weights — no host round-trip. The
paper reports 290 us/training step once host transfers are removed (§5).

Here the entire trial — environment (input pattern generation), anncore
emulation, observable digitization, R-STDP update — is ONE jitted function
(`make_trial_step`). The host-in-the-loop baseline (`host_loop_trial`)
pulls observables to the host between phases, reproducing the comparison
the paper makes.

The experiment is §5's pattern-discrimination task: 16 inputs with Poisson
background, patterns A/B on 5 (possibly overlapping) channels; even neurons
are rewarded for firing on A, odd neurons on B.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.bss2 import BSS2Config, BSS2
from repro.core import rules, synapse
from repro.core.anncore import AnnCore, AnnCoreState
from repro.core.ppu import VectorUnit
from repro.obs import trace as obs_trace
from repro.verif.mismatch import sample_instance


@dataclass(frozen=True)
class RSTDPConfig:
    n_inputs: int = 16
    n_neurons: int = 16
    pattern_size: int = 5
    overlap: float = 0.4          # fraction of shared channels (paper: 40%)
    trial_steps: int = 256        # dt steps per trial
    bg_prob: float = 0.008        # background spike prob / channel / dt
    pattern_repeats: int = 4      # pattern burst repetitions per trial
    eta: float = 16.0
    eta_homeo: float = 0.4        # escape term only — must stay well below
                                  # the eligibility term or it pins the
                                  # network at the firing threshold
    gamma: float = 0.3            # paper Eq. 2
    noise: float = 0.1            # random-walk xi (spike-level exploration
                                  # comes from the Poisson background)
    w_init: float = 20.0
    burst_width: int = 2          # consecutive dt steps per pattern burst
    fire_thresh: float = 1.0      # spikes to count as "fired"


class ExperimentState(NamedTuple):
    core: AnnCoreState
    w_signed: jnp.ndarray         # PPU-resident signed weights [.., I, C]
    mean_reward: jnp.ndarray      # [.., C]
    key: jnp.ndarray
    tele: Any = None              # obs.trace.Telemetry counters (None=off;
    #                               an empty pytree slot, so disabled runs
    #                               compile to the exact pre-telemetry
    #                               program)
    routed: Any = None            # wafer mode: [T, K, R] inter-chip events
    #                               the last trial deposited for this one
    #                               (None = single-chip, an empty slot)


def _patterns(ecfg: RSTDPConfig) -> Tuple[np.ndarray, np.ndarray]:
    """Channel sets for patterns A and B with the requested overlap."""
    k = ecfg.pattern_size
    n_shared = int(round(ecfg.overlap * k))
    a = list(range(k))
    b = a[:n_shared] + list(range(k, 2 * k - n_shared))
    mask_a = np.zeros(ecfg.n_inputs, np.float32)
    mask_b = np.zeros(ecfg.n_inputs, np.float32)
    mask_a[a] = 1
    mask_b[b] = 1
    return mask_a, mask_b


def make_experiment(cfg: BSS2Config = None, ecfg: RSTDPConfig = RSTDPConfig(),
                    instance_key=None, prefix=(), backend: str = "auto",
                    kernel_impl: str = "auto", rule_impl: str = "python",
                    vm_executor: str = "auto", block_size: int = None,
                    trace_block: int = None, kernel_block: int = None,
                    sparse_mode: str = None, sparse_threshold: float = None,
                    telemetry: bool = False, wafer: int = None,
                    wafer_topology: str = "all2all", wafer_relay: bool = True,
                    wafer_plan=None, wafer_ctx=None, link_budget: int = None,
                    link_mode: str = "auto", faults=None, blacklist=None):
    """Build the experiment closure set. Returns (init_fn, trial_fn, meta).

    The machine uses 2 rows per input (exc/inh pair, Dale's law: the PPU
    writes |w| to the row matching the sign — paper §5).

    ``backend``/``kernel_impl`` select the AnnCore emulation path (see
    repro.core.anncore): "auto" runs the fused hot path — correlation
    hoisted out of the dt scan, whole-trial synray matmul ("blocked" adds
    the time-blocked neuron window and is the auto pick on TPU) — with
    "oracle" kept as the per-step ground truth. ``block_size`` /
    ``trace_block`` / ``kernel_block`` override the blocked backend's
    time-block lengths (CPU membrane slab, current-trace slab, TPU
    kernel block; whole-experiment scans compose with any block size —
    T need not divide). ``sparse_mode``/``sparse_threshold`` control the
    event-sparse synaptic path ("auto"/"never"/"always" and its density
    gate — bit-identical output either way, see
    ``synapse.synaptic_current_window``).

    ``rule_impl`` selects how the §5 learning rule executes:
      "python"  the rule is the ``_signed_rule`` Python callable (default);
      "vm"      the vector part runs as a PPU-VM *program*
                (``repro.ppuvm.programs.signed_dw_program``) interpreted by
                the fixed-point SIMD executor inside the same jitted trial —
                the paper's hybrid-plasticity story with the rule as
                uploadable software instead of host code. The scalar glue
                (Eq. 2, xi random walk, Dale row rewrite) is identical, so
                the two paths differ only by Q8.8 fixed-point rounding of
                the dw term.

    ``vm_executor`` selects the VM implementation for ``rule_impl="vm"``
    (see ``repro.ppuvm.interp.EXECUTORS``): the default "auto" resolves
    to the trace-time specializer — the program words are a closed-over
    constant of the jitted trial, so the rule compiles to straight-line
    fixed-point ops with zero interpreter dispatch. All executors are
    bit-identical (tests/test_ppuvm_fuzz.py), so this is purely a
    performance axis.

    ``telemetry``: carry a jit-safe ``repro.obs.trace.Telemetry`` counter
    pytree through the training scan (``ExperimentState.tele``): spike /
    event totals, sparse-gate decisions and overflow fallbacks, VM
    saturation-rail hits, and the weight-update magnitude histogram.
    Off (default) the slot is ``None`` — an empty pytree, the compiled
    program is exactly the pre-telemetry one; on/off is bit-identical in
    spikes/weights (telemetry only reads the existing dataflow).

    ``wafer``: partition the experiment over K virtual chips
    (``repro.wafer``): the neuron columns split into K contiguous blocks
    (one per chip — the instance prefix becomes ``(K,)``), all 2I input
    rows are replicated per chip, and an ``InterChipRouter`` closes the
    trial loop — each trial's spikes are broadcast over the bus and
    arrive as relay-row events in the NEXT trial (``wafer_relay``; see
    ``repro.wafer.topology.s5_column_plan``). Mismatch draws, background
    events, and exploration noise are drawn at the MONOLITHIC shapes with
    the monolithic key stream and then rearranged onto the chips, so the
    learning trajectory is bit-identical for every chip count — the
    closed-loop half of the split-vs-monolithic contract. ``wafer_ctx``
    (a ``ShardingCtx``) turns on the shard_map link collectives and
    places each chip's state on its device;
    ``link_budget``/``link_mode`` are the router's bus-budget knobs.

    ``faults``: a ``repro.faults.FaultPlan`` (or sequence) injected into
    the emulated silicon — dead drivers/neurons, stuck weights, CADC
    corruption, VM-store bit-flips, dead/flaky wafer links. ``None`` is
    the identity: the fault-free experiment is the SAME jaxpr as before
    the subsystem existed. ``blacklist``: a ``repro.faults.Blacklist``
    (typically from ``repro.faults.screen``) applied ON TOP of the
    faults as the graceful-degradation reduction — blacklisted rows /
    neurons are masked exactly (``Blacklist.as_faults``), and
    blacklisted LINKS re-route over an intermediate chip
    (``repro.wafer.topology.reroute_plan``; forwarded traffic is counted
    in the ``link_reroutes`` telemetry counter, never silent).

    Args:
      cfg: ``BSS2Config`` chip geometry; ``None`` derives the reduced
        §5 geometry (``2*n_inputs`` rows x ``n_neurons`` cols) from
        ``ecfg``.
      ecfg: ``RSTDPConfig`` — the §5 experiment parameters (patterns,
        trial length, learning rates).
      instance_key: PRNG key for the virtual-instance mismatch draw
        (``None`` = fixed default key).
      prefix: instance-prefix shape for multi-instance fleets; must be
        ``()`` in wafer mode (the prefix becomes ``(K,)``).
      backend: "auto" | "oracle" | "fused" | "blocked" (see above).
      kernel_impl: "auto" | "pallas" | "interpret" | "ref" kernel choice
        for whichever backend runs.
      rule_impl: "python" | "vm" (see above).
      vm_executor: executor for ``rule_impl="vm"`` (see above).
      block_size / trace_block / kernel_block: blocked-backend time
        blocks (see above).
      sparse_mode / sparse_threshold: event-sparse synaptic path gate
        (see above).
      telemetry: thread the jit-safe counter pytree (see above).
      wafer: chip count K (``None`` = single chip).
      wafer_topology: "all2all" | "ring" link graph for the built-in
        §5 split.
      wafer_relay: allow the §5 split's relay rows on ring topologies.
      wafer_plan: explicit validated ``WaferPlan`` replacing the
        built-in ``s5_column_plan`` — the ``repro.mapper`` integration
        point; geometry must match ``(2*n_inputs, n_neurons/K)``.
      wafer_ctx: ``ShardingCtx`` enabling shard_map link collectives.
      link_budget / link_mode: router bus-budget knobs
        (``repro.wafer.router.InterChipRouter``).
      faults: ``FaultPlan`` defect injection (``None`` = same jaxpr).
      blacklist: ``Blacklist`` graceful-degradation reduction.

    Returns:
      ``(init_fn, trial_fn, meta)`` — jit-ready init/trial closures and
      a dict of host-side objects (core, ppu, router, plan, ...).

    Contracts (each enforced by a tier-1 test — see docs/exactness.md):
      backends bit-identical        tests/test_blocked.py
      sparse path bit-identical     tests/test_sparse.py
      VM executors bit-identical    tests/test_ppuvm_fuzz.py
      telemetry on/off identical    tests/test_obs.py
      split == monolithic           tests/test_wafer.py
      faults=None same jaxpr        tests/test_faults.py
      wafer_plan == built-in split  tests/test_mapper.py (TestHybridIntegration)
    """
    if cfg is None:
        cfg = dataclasses.replace(
            BSS2.reduced(), n_rows=2 * ecfg.n_inputs, n_cols=ecfg.n_neurons)
    assert cfg.n_rows == 2 * ecfg.n_inputs and cfg.n_cols == ecfg.n_neurons
    K = wafer
    if K:
        from repro.wafer import InterChipRouter, s5_column_plan
        assert prefix == (), "wafer mode owns the instance prefix"
        assert ecfg.n_neurons % K == 0 and (ecfg.n_neurons // K) % 2 == 0, \
            "need an even per-chip column count (reward parity)"
        c_loc = ecfg.n_neurons // K
        chip_cfg = dataclasses.replace(cfg, n_cols=c_loc)
        prefix = (K,)
        if wafer_plan is not None:
            # a mapper-built (or hand-built) placement replaces the
            # hard-coded §5 column split — any validated WaferPlan with
            # the experiment's per-chip geometry runs here
            plan = wafer_plan
            assert plan.topology.n_chips == K, \
                f"wafer_plan is for {plan.topology.n_chips} chips, wafer={K}"
            assert (plan.n_rows, plan.n_cols) == (2 * ecfg.n_inputs, c_loc), \
                (f"wafer_plan geometry {(plan.n_rows, plan.n_cols)} != "
                 f"{(2 * ecfg.n_inputs, c_loc)}")
        else:
            plan = s5_column_plan(K, ecfg.n_inputs, ecfg.n_neurons,
                                  relay=wafer_relay, kind=wafer_topology)
    else:
        c_loc = ecfg.n_neurons
        chip_cfg = cfg
        plan = None
    mask_a, mask_b = _patterns(ecfg)
    mask_a, mask_b = jnp.asarray(mask_a), jnp.asarray(mask_b)
    even = (jnp.arange(ecfg.n_neurons) % 2 == 0).astype(jnp.float32)
    if K:
        even = even.reshape(K, c_loc)

    if instance_key is None:
        instance_key = jax.random.PRNGKey(7)
    if K:
        # the fleet is ONE partitioned instance: sample the monolithic
        # mismatch realisation, then slice columns per chip / replicate
        # the (shared) row-side parameters
        inst_g = sample_instance(cfg, instance_key, ())
        _cols = lambda x: jnp.reshape(x, (K, c_loc))
        _rows = lambda x: jnp.broadcast_to(x, (K, x.shape[-1]))
        inst = dict(
            neuron_params=jax.tree.map(_cols, inst_g["neuron_params"]),
            weight_gain=_cols(inst_g["weight_gain"]),
            stp_offset=_rows(inst_g["stp_offset"]),
            stp_calib=_rows(inst_g["stp_calib"]),
            cadc_offset=_cols(inst_g["cadc_offset"]),
            cadc_gain=_cols(inst_g["cadc_gain"]))
    else:
        inst = sample_instance(cfg, instance_key, prefix)
    block_kw = {k: v for k, v in dict(
        block_size=block_size, trace_block=trace_block,
        kernel_block=kernel_block, sparse_mode=sparse_mode,
        sparse_threshold=sparse_threshold).items() if v is not None}
    # fault overlay: injection plans first, the blacklist reduction last
    # (its masks dominate the faults they cover — the exactness contract)
    overlay = faults
    if blacklist is not None and blacklist.total:
        from repro.faults import chain as faults_chain
        overlay = faults_chain(
            faults, blacklist.as_faults(inst, cfg.cadc_bits)
            if (blacklist.n_rows or blacklist.n_neurons) else None)
        if blacklist.links:
            assert K, "link blacklists need wafer mode"
            from repro.faults.model import as_plans, remap_link_faults
            from repro.wafer.topology import reroute_plan
            old_links = plan.topology.links()
            plan, _n_re = reroute_plan(plan, blacklist.links)
            new_links = plan.topology.links()
            if new_links != old_links:
                # ring -> all2all promotion re-indexed the link space:
                # carry injected link faults over by pair identity
                overlay = tuple(remap_link_faults(p, old_links, new_links)
                                for p in as_plans(overlay))
    if K:
        router = InterChipRouter(plan, ctx=wafer_ctx,
                                 link_budget=link_budget,
                                 link_mode=link_mode, faults=overlay)
    else:
        router = None
    # const_addr: a row driven by the inputs alone carries one source (input
    # i -> rows 2i/2i+1, address 0 throughout), so the CPU's dense path may
    # resolve the address-match mask once per trial. A plan that delivers
    # into rows breaks the promise: the relay rows carry address 0 on
    # external events and the route's address (63) on relayed spikes, in
    # different steps of one trial.
    const_addr = plan is None or not plan.relay_rows().any()
    core = AnnCore(chip_cfg, inst, backend=backend, kernel_impl=kernel_impl,
                   const_addr=const_addr, faults=overlay, **block_kw)
    ppu = VectorUnit(chip_cfg, inst, faults=overlay)

    def init(key) -> ExperimentState:
        st = core.init_state(prefix)
        w0 = ecfg.w_init * jnp.ones((*prefix, ecfg.n_inputs, c_loc))
        st = st._replace(syn=_write_signed(st.syn, w0))
        state = ExperimentState(
            core=st, w_signed=w0,
            mean_reward=jnp.zeros((*prefix, c_loc)), key=key,
            tele=obs_trace.init_telemetry() if telemetry else None,
            routed=router.init_buffer(ecfg.trial_steps) if K else None)
        if K and wafer_ctx is not None and wafer_ctx.mesh is not None:
            state = _place_chips(state)
        return state

    def _place_chips(state):
        """Wafer mode on a mesh: every chip-major leaf ([K, ...]) and the
        routed grid ([T, K, R]) get the instance sharding, so each device
        holds its own emulated chips' state (the same axis the router's
        link collectives run over)."""
        def chip_major(x):
            return jax.lax.with_sharding_constraint(
                x, wafer_ctx.instance_sharding(x.shape))
        placed = jax.tree.map(chip_major, (state.core, state.w_signed,
                                           state.mean_reward))
        routed = jax.lax.with_sharding_constraint(
            state.routed, jax.sharding.NamedSharding(
                wafer_ctx.mesh, wafer_ctx.link_specs(1, 3)[0]))
        return state._replace(core=placed[0], w_signed=placed[1],
                              mean_reward=placed[2], routed=routed)

    def _write_signed(syn, w_signed):
        w_exc = jnp.clip(w_signed, 0, None)
        w_inh = jnp.clip(-w_signed, 0, None)
        w_rows = jnp.stack([w_exc, w_inh], axis=-3)   # [.., 2, I, C]
        shape = (*w_signed.shape[:-2], 2 * ecfg.n_inputs, c_loc)
        w_rows = w_rows.transpose(
            *range(w_signed.ndim - 2), -2, -3, -1).reshape(shape)
        return syn._replace(weights=synapse.quantize_weight(w_rows))
    _write_signed.__doc__ = "interleave exc/inh rows: row 2i exc, 2i+1 inh"

    # wafer mode: events and exploration noise are DRAWN monolithically
    # (jax.random is shape-dependent, so per-chip draws would break the
    # bit-for-bit chip-count invariance) and then placed onto the chips
    gen_prefix = () if K else prefix

    # burst schedule is static per experiment — precomputed once here, not
    # rebuilt inside every (possibly scanned) trial
    T = ecfg.trial_steps
    _burst_times = np.linspace(T // 8, T - T // 8, ecfg.pattern_repeats,
                               dtype=np.float32).astype(np.int64)
    _dt_to_burst = np.arange(T)[:, None] - _burst_times[None, :]
    is_burst = jnp.asarray(
        np.any((_dt_to_burst >= 0) & (_dt_to_burst < ecfg.burst_width),
               axis=1).astype(np.float32)
        .reshape(T, *([1] * len(gen_prefix)), 1))

    def _gen_events(key, stim):
        """Event stream [T, .., 2I] for stimulus in {0:none, 1:A, 2:B}."""
        kb, kp = jax.random.split(key)
        bg = (jax.random.uniform(kb, (T, *gen_prefix, ecfg.n_inputs))
              < ecfg.bg_prob).astype(jnp.float32)
        # pattern: synchronized bursts on the pattern channels
        pat_mask = jnp.where(stim == 1, mask_a,
                             jnp.where(stim == 2, mask_b,
                                       jnp.zeros_like(mask_a)))
        pat = is_burst * pat_mask.reshape(*([1] * (1 + len(gen_prefix))), -1)
        ch = jnp.clip(bg + pat, 0, 1)
        # input i drives rows 2i (exc) and 2i+1 (inh) with the same events
        ev = jnp.repeat(ch, 2, axis=-1)
        if K:
            # every chip sees the full (replicated) stimulus
            ev = jnp.broadcast_to(ev[:, None, :], (T, K, ev.shape[-1]))
        addr = jnp.zeros(ev.shape, jnp.int8)
        return ev, addr

    def _draw_xi(sub):
        """Exploration noise, monolithic layout in wafer mode: the global
        [I, n_neurons] draw reshaped so chip k's column block c equals
        global column k * c_loc + c."""
        if K:
            g = jax.random.normal(sub, (ecfg.n_inputs, ecfg.n_neurons))
            return ecfg.noise * jnp.transpose(
                g.reshape(ecfg.n_inputs, K, c_loc), (1, 0, 2))
        return ecfg.noise * jax.random.normal(
            sub, (*prefix, ecfg.n_inputs, c_loc))

    def _reward(rates, stim):
        fired = (rates >= ecfg.fire_thresh).astype(jnp.float32)
        own_shown = jnp.where(stim == 1, even,
                              jnp.where(stim == 2, 1.0 - even,
                                        jnp.zeros_like(even)))
        return jnp.where(own_shown > 0, fired, 1.0 - fired)

    if rule_impl == "vm":
        from repro.ppuvm import isa as _visa, programs as _vprog
        _dw_words = jnp.asarray(_vprog.signed_dw_program(
            eta=ecfg.eta, eta_homeo=ecfg.eta_homeo,
            fire_thresh=ecfg.fire_thresh))
    elif rule_impl != "python":
        raise ValueError(f"unknown rule_impl {rule_impl!r}")

    def _vm_signed_update(cs, state, reward, k_rule, tele):
        """§5 rule with the vector part as a PPU-VM program: the program
        computes the per-row dw readout (register 0); the scalar core
        applies it to the PPU-resident signed float weights, adds the xi
        walk, and rewrites both Dale rows — mirroring ``_signed_rule``."""
        qc, qa = ppu.read_correlation(cs.corr)
        mod = jnp.stack([reward - state.mean_reward, reward], axis=0)
        cs2, regs = ppu.run_program(cs, _dw_words, mod=mod,
                                    executor=vm_executor)
        tele = obs_trace.count_vm(tele, regs)
        dw = regs[0][..., 0::2, :].astype(jnp.float32) / _visa.ONE
        key, sub = jax.random.split(k_rule)
        xi = _draw_xi(sub)
        w_signed = jnp.clip(state.w_signed + dw + xi, -45.0, 45.0)
        mean_r = state.mean_reward + ecfg.gamma * (
            reward - state.mean_reward)                         # Eq. 2
        cs2 = cs2._replace(syn=_write_signed(cs2.syn, w_signed))
        obs = dict(causal=qc, acausal=qa)
        return cs2, dict(mean_reward=mean_r, w_signed=w_signed), obs, tele

    def _trial_with(state, stim, ev, addr, k_rule, key_next):
        """Trial body given pregenerated events + keys (shared between the
        per-trial dispatch path and the whole-experiment scan)."""
        if router is not None:
            # close the wafer loop: last trial's routed spikes merge into
            # this trial's inputs, this trial's spikes go on the bus
            cs, core_out = core.run_routed(state.core, state.routed, ev,
                                           addr, router,
                                           telemetry=state.tele)
        else:
            cs, core_out = core.run(state.core, ev, addr,
                                    telemetry=state.tele)
        tele = core_out.get("telemetry")
        rates = cs.rate_counters
        with obs_trace.scope("ppu_rule"):
            r = _reward(rates, stim)
            tele = obs_trace.count_trial(tele, rates)

            # PPU: R-STDP on the signed PPU weights, using exc-row
            # eligibility
            if rule_impl == "vm":
                cs2, rule_state, obs, tele = _vm_signed_update(
                    cs, state, r, k_rule, tele)
            else:
                cs2, rule_state, obs = ppu.apply_rule(
                    _signed_rule, cs,
                    dict(mean_reward=state.mean_reward, key=k_rule,
                         w_signed=state.w_signed),
                    reward=r)
            tele = obs_trace.count_dw(tele, state.w_signed,
                                      rule_state["w_signed"])
        new = ExperimentState(core=cs2, w_signed=rule_state["w_signed"],
                              mean_reward=rule_state["mean_reward"],
                              key=key_next, tele=tele,
                              routed=core_out.get("routed"))
        elig = (obs["causal"][..., 0::2, :]
                - obs["acausal"][..., 0::2, :]).astype(jnp.float32) / 255.0
        metrics = dict(reward=r, mean_reward=rule_state["mean_reward"],
                       rates=rates, stim=stim, elig=elig,
                       w=rule_state["w_signed"])
        return new, metrics

    def trial(state: ExperimentState, stim) -> Tuple[ExperimentState, Dict]:
        """One fused training trial. stim: int32 in {0,1,2} (the PPU's
        simulated environment picks it upstream or it is scanned over)."""
        with obs_trace.scope("event_generation"):
            key, k_ev, k_rule = jax.random.split(state.key, 3)
            ev, addr = _gen_events(k_ev, stim)
        return _trial_with(state, stim, ev, addr, k_rule, key)

    def scanned_training(state: ExperimentState, stims):
        """The whole experiment as ONE program: a lax.scan of trials.

        The per-trial PRNG key chain is replayed up front (exactly the
        stream ``trial`` would consume), so all trials' Poisson background
        events are generated in ONE batched draw instead of T x n_trials
        tiny ones — then the scan body is pure emulation + PPU update.
        Bit-identical to dispatching ``trial`` per trial from Python."""
        n = stims.shape[0]

        def key_body(k, _):
            k2, k_ev, k_rule = jax.random.split(k, 3)
            return k2, (k2, k_ev, k_rule)

        with obs_trace.scope("event_generation"):
            _, (keys_next, k_evs, k_rules) = jax.lax.scan(
                key_body, state.key, None, length=n)
            ev_all, addr_all = jax.vmap(_gen_events)(k_evs, stims)

        def body(st, xs):
            stim, ev, addr, k_rule, key_next = xs
            return _trial_with(st, stim, ev, addr, k_rule, key_next)

        return jax.lax.scan(body, state,
                            (stims, ev_all, addr_all, k_rules, keys_next))

    def _signed_rule(w_rows, obs, rule_state, *, reward):
        """R-STDP on the signed input-level weights; rewrite both rows."""
        causal = obs["causal"][..., 0::2, :]       # exc rows carry the
        acausal = obs["acausal"][..., 0::2, :]     # pre-spike correlations
        elig = (causal - acausal).astype(jnp.float32) / 255.0
        mod = (reward - rule_state["mean_reward"])[..., None, :]
        key, sub = jax.random.split(rule_state["key"])
        xi = _draw_xi(sub)
        dw = ecfg.eta * mod * elig
        # homeostatic punishment (PPU rate counters): firing when the trial
        # earned no reward uniformly depresses the neuron's whole column.
        # Self-limiting: once the neuron only fires on its own pattern,
        # (1 - R) * fired == 0 and the term vanishes. Without it the
        # excitatory drive rails at w_max (see R-STDP bring-up log).
        # fired & unrewarded -> uniform depression; silent & unrewarded
        # (own pattern missed) -> uniform potentiation. Fixed point: fire
        # exactly on the own pattern (then (1-R) == 0 and the term is gone).
        fired = (obs["rates"] >= ecfg.fire_thresh).astype(jnp.float32)
        dw = dw + ecfg.eta_homeo * (
            (1.0 - reward) * (1.0 - 2.0 * fired))[..., None, :]
        w_signed = rule_state["w_signed"] + dw + xi
        w_signed = jnp.clip(w_signed, -45.0, 45.0)
        mean_r = rule_state["mean_reward"] + ecfg.gamma * (
            reward - rule_state["mean_reward"])                 # Eq. 2
        new_syn = _write_signed(
            synapse.SynapseArray(w_rows.astype(jnp.int8),
                                 jnp.zeros_like(w_rows, dtype=jnp.int8)),
            w_signed)
        return new_syn.weights.astype(jnp.float32), dict(
            mean_reward=mean_r, key=key, w_signed=w_signed)

    meta = dict(cfg=cfg, ecfg=ecfg, inst=inst, core=core, ppu=ppu,
                mask_a=mask_a, mask_b=mask_b, even=even,
                scanned_training=scanned_training, router=router)
    return init, trial, meta


def make_scanned_training(scanned_training):
    """Jit the whole-experiment program (``meta["scanned_training"]``):
    ONE dispatch for the full §5 run, state buffers donated, metrics back
    stacked [n_trials, ...] — the machine-model analogue of the paper's
    claim that hybrid plasticity removes the host from the training loop
    entirely."""
    return jax.jit(scanned_training, donate_argnums=(0,))


def run_training(n_trials: int = 300, ecfg: RSTDPConfig = RSTDPConfig(),
                 seed: int = 0, cfg: BSS2Config = None, fused: bool = True,
                 scan: bool = None, backend: str = "auto",
                 rule_impl: str = "python", vm_executor: str = "auto",
                 block_size: int = None, trace_block: int = None,
                 kernel_block: int = None, sparse_mode: str = None,
                 sparse_threshold: float = None, telemetry: bool = False,
                 wafer: int = None, wafer_topology: str = "all2all",
                 wafer_relay: bool = True, wafer_plan=None, wafer_ctx=None,
                 link_budget: int = None, link_mode: str = "auto",
                 faults=None, blacklist=None):
    """Full §5 experiment. Returns the metrics history (stacked).

    Modes:
      fused=True, scan=True   ONE jitted lax.scan over all trials (default)
      fused=True, scan=False  per-trial jit dispatch from a Python loop
                              (the host-dispatch baseline)
      fused=False             host-in-the-loop: observables cross the host
                              boundary every trial (the slow path §5 kills)

    ``telemetry=True`` threads the jit-safe counter pytree through the
    whole run (bit-identical metrics either way) and returns the host
    summary under ``out["telemetry"]``.

    Args:
      n_trials: number of closed-loop trials to run.
      ecfg / cfg: experiment / chip geometry configs (see
        ``make_experiment``).
      seed: derives both the mismatch instance key (``PRNGKey(seed)``)
        and the run key (``PRNGKey(seed + 1)``).
      fused / scan: execution mode (see Modes above).
      backend, rule_impl, vm_executor, block_size, trace_block,
      kernel_block, sparse_mode, sparse_threshold, telemetry, wafer,
      wafer_topology, wafer_relay, wafer_plan, wafer_ctx, link_budget,
      link_mode, faults, blacklist: forwarded verbatim to
        ``make_experiment`` — every knob documented there (and in the
        knob matrix of docs/architecture.md) applies here.

    Returns:
      ``(out, state, meta)``: ``out`` the stacked metrics history
      (``reward``, ``w_signed_final``, optionally ``telemetry``),
      ``state`` the final ``ExperimentState``, ``meta`` the
      ``make_experiment`` host objects.

    Contract pointers: tests/test_rstdp.py (learning curve),
    tests/test_scan_path.py (fused/scan modes bit-identical),
    tests/test_wafer.py (wafer=K trajectory == monolithic),
    tests/test_mapper.py::TestHybridIntegration (explicit wafer_plan).
    """
    init, trial, meta = make_experiment(cfg=cfg, ecfg=ecfg,
                                        instance_key=jax.random.PRNGKey(seed),
                                        backend=backend, rule_impl=rule_impl,
                                        vm_executor=vm_executor,
                                        block_size=block_size,
                                        trace_block=trace_block,
                                        kernel_block=kernel_block,
                                        sparse_mode=sparse_mode,
                                        sparse_threshold=sparse_threshold,
                                        telemetry=telemetry, wafer=wafer,
                                        wafer_topology=wafer_topology,
                                        wafer_relay=wafer_relay,
                                        wafer_plan=wafer_plan,
                                        wafer_ctx=wafer_ctx,
                                        link_budget=link_budget,
                                        link_mode=link_mode,
                                        faults=faults, blacklist=blacklist)
    state = init(jax.random.PRNGKey(seed + 1))
    stims = jnp.asarray(np.resize([1, 2, 0], n_trials), jnp.int32)
    if scan is None:
        scan = fused

    if fused and scan:
        scanned = make_scanned_training(meta["scanned_training"])
        state, hist = scanned(state, stims)
        out = {k: np.asarray(v) for k, v in hist.items()}
    else:
        jtrial = jax.jit(trial)
        hist = []
        for i in range(n_trials):
            if fused:
                state, m = jtrial(state, stims[i])
            else:
                state, m = host_loop_trial(trial, state, stims[i])
            hist.append(m)
        out = {k: np.stack([np.asarray(h[k]) for h in hist])
               for k in hist[0]}
    out["w_signed_final"] = np.asarray(state.w_signed)
    if telemetry:
        out["telemetry"] = obs_trace.summary(state.tele)
    return out, state, meta


def host_loop_trial(trial, state, stim):
    """Host-in-the-loop baseline: every observable crosses the host boundary
    (device_get / device_put) before the update — the slow path the paper's
    hybrid architecture eliminates."""
    state = jax.tree.map(lambda x: jax.device_put(jax.device_get(x)), state)
    new, m = jax.jit(trial)(state, stim)
    m = {k: jax.device_get(v) for k, v in m.items()}
    return new, m


# ---------------------------------------------------------------------------
# Dry-run cell for --arch bss2: pod-scale batched hybrid-plasticity step
# ---------------------------------------------------------------------------

def lower_bss2_cell(shape, ctx, mesh_cfg):
    """Lower the fused trial step for a *fleet* of full-size BSS-2 machine
    instances: instances over the data axes, synapse columns over model.

    This is the scale-up the paper's Discussion anticipates (several
    anncore+PPU blocks per reticle): shape.global_batch independent chips
    learning in parallel, one jitted program.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.config import SHAPES
    from repro.analysis.roofline import RooflineReport, collective_seconds, \
        parse_collectives, hbm_bytes_estimate

    n_inst = max(shape.global_batch, 16)
    cfg = BSS2  # full-size: 256 rows x 512 cols
    ecfg = RSTDPConfig(n_inputs=cfg.n_rows // 2, n_neurons=cfg.n_cols,
                       pattern_size=24, trial_steps=128)
    # the lowered cell is the production hot path ("auto" = the blocked
    # time-window backend on TPU, fused elsewhere): whole-trial synray
    # matmul + hoisted correlation window + time-blocked neuron scan, all
    # with the instance fleet on the kernels' instance grid axis
    init, trial, meta = make_experiment(cfg=cfg, ecfg=ecfg, prefix=(n_inst,),
                                        backend="auto")

    def batched_trial(state, stim):
        return trial(state, stim)

    mesh = ctx.mesh
    state_abs = jax.eval_shape(init, jax.random.PRNGKey(0))

    def spec_for(path_leaf):
        # instances (leading dim n_inst) over data axes; trailing synapse
        # col dim over model where divisible — the INSTANCE rule is the
        # mesh-side twin of the kernels' instance grid axis
        shp = path_leaf.shape
        if len(shp) >= 1 and shp[0] == n_inst:
            sh = ctx.instance_sharding(shp, cols=cfg.n_cols)
            if sh is not None:
                return sh
        parts = [None] * len(shp)
        if len(shp) >= 1 and shp[-1] == cfg.n_cols:
            parts[-1] = "model"
        return NamedSharding(mesh, P(*parts))

    st_sh = jax.tree.map(spec_for, state_abs)
    with mesh:
        fn = jax.jit(batched_trial,
                     in_shardings=(st_sh, NamedSharding(mesh, P())),
                     donate_argnums=(0,))
        lowered = fn.lower(state_abs, jax.ShapeDtypeStruct((), jnp.int32))
        compiled = lowered.compile()

    txt = compiled.as_text()
    ca = compiled.cost_analysis()
    ma = compiled.memory_analysis()
    colls = parse_collectives(txt)
    hbm = hbm_bytes_estimate(txt)
    # MODEL_FLOPS for the machine model: synapse matmul + neuron updates
    flops_trial = (2 * cfg.n_rows * cfg.n_cols       # event matmul
                   + 40 * cfg.n_cols                 # neuron/corr updates
                   + 4 * cfg.n_rows * cfg.n_cols     # correlation outer
                   ) * ecfg.trial_steps * n_inst
    from repro.config import get_arch
    rep = RooflineReport(
        arch="bss2", shape=shape.name,
        mesh="2x16x16" if mesh_cfg.multi_pod else "16x16",
        flops_per_dev=float(ca.get("flops", 0.0)),
        bytes_per_dev=float(ca.get("bytes accessed", 0.0)),
        hbm_bytes_per_dev=float(hbm["rw"]), hbm_by_kind=hbm["by_kind"],
        transcendentals=float(ca.get("transcendentals", 0.0)),
        coll=colls, coll_sec=collective_seconds(colls),
        temp_bytes=int(ma.temp_size_in_bytes),
        arg_bytes=int(ma.argument_size_in_bytes),
        out_bytes=int(ma.output_size_in_bytes),
        model_flops_global=float(flops_trial),
        n_devices=mesh_cfg.n_devices, step_kind="train")
    return rep, compiled
