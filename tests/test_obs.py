"""Telemetry subsystem contract: free when off, honest when on.

Three invariants pin down ``repro.obs``:

  * **bit-exactness** — telemetry on/off produces IDENTICAL spikes,
    weights, and VM state (``assert_array_equal``, not tolerance) across
    the oracle/fused/blocked backends, the sparse routes, and the VM
    rule: the counters only *read* values the emulation already computes;
  * **counter correctness** — every counter matches a hand-counted
    NumPy oracle on the same inputs (events in, spikes out, routing
    decisions, saturation hits, |dw| histogram bins);
  * **zero retrace** — emitting (or re-emitting) the host summary/report
    never retraces the compiled training program.

Plus the layer scopes (each declared scope names ops of the compiled
program, and the scopes add no instruction), the first-divergence
locator (``repro.verif.mismatch``), the phase timer, the run report, and
the specializer-cache eviction accounting.

``ANNCORE_KERNEL_IMPL`` (default "auto") forces the kernel impl — the
tier-2 CI observability job runs this suite under "interpret".
"""
import contextlib
import dataclasses
import os
import re
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.bss2 import BSS2
from repro.core import synapse
from repro.core.anncore import AnnCore
from repro.core.hybrid import (RSTDPConfig, make_experiment,
                               make_scanned_training, run_training)
from repro.obs import report as obs_report
from repro.obs import timing as obs_timing
from repro.obs import trace as obs_trace
from repro.ppuvm import isa, programs, specialize
from repro.verif import playback as pb
from repro.verif.mismatch import (Divergence, first_divergence,
                                  ideal_instance, sample_instance)

KERNEL_IMPL = os.environ.get("ANNCORE_KERNEL_IMPL", "auto")


def _events(T, R, key=0, p=0.05):
    ks = jax.random.split(jax.random.PRNGKey(key), 2)
    ev = (jax.random.uniform(ks[0], (T, R)) < p).astype(jnp.float32)
    ad = jnp.zeros((T, R), jnp.int8)
    return ev, ad


# ---------------------------------------------------------------------------
# Bit-exactness: telemetry must never touch the numbers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["oracle", "fused", "blocked"])
def test_training_on_off_bit_exact(backend):
    on, s_on, _ = run_training(n_trials=3, seed=0, backend=backend,
                               telemetry=True)
    off, s_off, _ = run_training(n_trials=3, seed=0, backend=backend,
                                 telemetry=False)
    np.testing.assert_array_equal(on["w_signed_final"],
                                  off["w_signed_final"])
    for k in off:
        if k != "w_signed_final":
            np.testing.assert_array_equal(np.asarray(on[k]),
                                          np.asarray(off[k]), err_msg=k)
    tele = on["telemetry"]
    assert tele["trials"] == 3
    assert tele["steps"] == 3 * 256
    assert tele["out_spikes"] > 0
    assert tele["dw_updates"] == 3
    assert "telemetry" not in off


def test_training_vm_rule_on_off_bit_exact():
    on, _, _ = run_training(n_trials=3, seed=0, rule_impl="vm",
                            telemetry=True)
    off, _, _ = run_training(n_trials=3, seed=0, rule_impl="vm",
                             telemetry=False)
    np.testing.assert_array_equal(on["w_signed_final"],
                                  off["w_signed_final"])
    assert on["telemetry"]["vm_runs"] == 3


def test_window_on_off_bit_exact_all_routes():
    T, R, C = 512, 64, 64
    ev, ad = _events(T, R, p=0.01)
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    w = jax.random.randint(ks[0], (R, C), 0, 64, jnp.int8)
    a = jnp.zeros((R, C), jnp.int8)
    for mode in ("auto", "never", "always"):
        i_off = synapse.synaptic_current_window(
            w, a, ev, ad, 1.0, impl=KERNEL_IMPL, sparse=mode)
        i_on, tele = synapse.synaptic_current_window(
            w, a, ev, ad, 1.0, impl=KERNEL_IMPL, sparse=mode,
            telemetry=obs_trace.init_telemetry())
        np.testing.assert_array_equal(np.asarray(i_off), np.asarray(i_on),
                                      err_msg=mode)
        assert tele is not None


# ---------------------------------------------------------------------------
# Counter correctness vs hand-counted oracles
# ---------------------------------------------------------------------------

def test_run_counters_match_hand_count():
    cfg = BSS2.reduced()
    core = AnnCore(cfg, ideal_instance(cfg), kernel_impl=KERNEL_IMPL)
    state = state0 = core.init_state()
    state = state._replace(syn=state.syn._replace(
        weights=jnp.full((cfg.n_rows, cfg.n_cols), 45, jnp.int8)))
    ev, ad = _events(96, cfg.n_rows, p=0.04)
    tele0 = obs_trace.init_telemetry()
    state, out = core.run(state, ev, ad, telemetry=tele0)
    s = obs_trace.summary(out["telemetry"])
    assert s["steps"] == 96
    assert s["in_events"] == int(np.count_nonzero(np.asarray(ev)))
    assert s["out_spikes"] == int(np.asarray(out["spikes"]).sum())
    del state0


def test_gate_counters_sparse_fit_and_overflow():
    T, R, C = 1024, 256, 256
    ev, ad = _events(T, R, key=3, p=0.002)
    w = jnp.full((R, C), 20, jnp.int8)
    a = jnp.zeros((R, C), jnp.int8)
    n_ev = int(np.count_nonzero(np.asarray(ev)))
    k_max = int(np.asarray(ev).astype(bool).sum(axis=1).max())

    # fitting window -> routed sparse, census maxima recorded
    _, tele = synapse.synaptic_current_window(
        w, a, ev, ad, 1.0, impl=KERNEL_IMPL, sparse="auto",
        telemetry=obs_trace.init_telemetry())
    s = obs_trace.summary(tele)
    assert s["gated_windows"] == 1 and s["sparse_windows"] == 1
    assert s["dense_windows"] == 0 and s["overflow_fallbacks"] == 0
    assert s["census_events_max"] == n_ev
    assert s["census_k_max"] == k_max

    # undersized capacity -> observable overflow fallback, dense result
    i_over, tele = synapse.synaptic_current_window(
        w, a, ev, ad, 1.0, impl=KERNEL_IMPL, sparse="auto", max_events=4,
        telemetry=obs_trace.init_telemetry())
    s = obs_trace.summary(tele)
    assert s["overflow_fallbacks"] == 1 and s["dense_windows"] == 1
    assert s["sparse_windows"] == 0
    i_dense = synapse.synaptic_current_window(
        w, a, ev, ad, 1.0, impl=KERNEL_IMPL, sparse="never")
    np.testing.assert_array_equal(np.asarray(i_over), np.asarray(i_dense))


def test_gate_counters_static_routes():
    # below the work floor: compiles to the pure dense program, counted
    # as a static dense route (gated_windows stays 0)
    ev, ad = _events(32, 16, p=0.1)
    w = jnp.ones((16, 16), jnp.int8)
    a = jnp.zeros((16, 16), jnp.int8)
    _, tele = synapse.synaptic_current_window(
        w, a, ev, ad, 1.0, impl=KERNEL_IMPL, sparse="auto",
        telemetry=obs_trace.init_telemetry())
    s = obs_trace.summary(tele)
    assert s["dense_windows"] == 1 and s["gated_windows"] == 0

    _, tele = synapse.synaptic_current_window(
        w, a, ev, ad, 1.0, impl=KERNEL_IMPL, sparse="always",
        telemetry=obs_trace.init_telemetry())
    assert obs_trace.summary(tele)["sparse_windows"] == 1


def test_count_vm_saturation_hand_count():
    regs = jnp.stack([
        jnp.full((4, 4), isa.I16MAX, jnp.int32),
        jnp.full((4, 4), isa.I16MIN, jnp.int32),
        jnp.zeros((4, 4), jnp.int32),
    ])
    tele = obs_trace.count_vm(obs_trace.init_telemetry(), regs)
    s = obs_trace.summary(tele)
    assert s["vm_runs"] == 1
    assert s["vm_sat_hits"] == 32          # two full [4,4] planes
    assert obs_trace.count_vm(None, regs) is None


def test_dw_histogram_hand_count():
    w_old = jnp.zeros((8,), jnp.float32)
    w_new = jnp.asarray([0.0, 1/512, 0.1, 0.3, 1.5, 5.0, 31.0, 40.0],
                        jnp.float32)
    tele = obs_trace.count_dw(obs_trace.init_telemetry(), w_old, w_new)
    s = obs_trace.summary(tele)
    dw = np.abs(np.asarray(w_new))
    expect = np.zeros(obs_trace.DW_BINS, np.int64)
    for b in np.searchsorted(obs_trace.DW_EDGES, dw):
        expect[b] += 1
    assert s["dw_hist"] == expect.tolist()
    assert s["dw_updates"] == 1
    assert s["dw_abs_max"] == pytest.approx(40.0)


def test_dw_histogram_matches_searchsorted_bins():
    """|dw| on the edges fall in the bin below them, as with
    ``searchsorted``; random |dw| of three magnitudes bin as it does."""
    rng = np.random.default_rng(0)
    for scale in (0.01, 1.0, 50.0):
        w = rng.normal(0.0, scale, (3, 16, 16)).astype(np.float32)
        w[0, 0, :len(obs_trace.DW_EDGES)] = obs_trace.DW_EDGES
        tele = obs_trace.count_dw(obs_trace.init_telemetry(),
                                  np.zeros_like(w), w)
        expect = np.bincount(np.searchsorted(obs_trace.DW_EDGES,
                                             np.abs(w).ravel()),
                             minlength=obs_trace.DW_BINS)
        np.testing.assert_array_equal(np.asarray(tele.dw_hist), expect)


def test_update_helpers_identity_on_none():
    assert obs_trace.count_run(None, jnp.zeros((4, 4)),
                               jnp.zeros((4, 4))) is None
    assert obs_trace.count_route(None, sparse=True) is None
    assert obs_trace.count_trial(None, jnp.zeros(4)) is None
    assert obs_trace.count_dw(None, jnp.zeros(4), jnp.ones(4)) is None
    assert obs_trace.summary(None) is None


def test_init_telemetry_distinct_buffers():
    # the training scan donates its carry: duplicate buffers in the
    # telemetry pytree would make donation reject the dispatch
    tele = obs_trace.init_telemetry()
    ptrs = [x.unsafe_buffer_pointer() for x in tele]
    assert len(set(ptrs)) == len(ptrs)


# ---------------------------------------------------------------------------
# Zero retrace: report emission is a pure host-side read
# ---------------------------------------------------------------------------

def test_summary_emission_zero_retrace():
    from repro.core.hybrid import make_experiment
    init, _, meta = make_experiment(instance_key=jax.random.PRNGKey(0),
                                    telemetry=True)
    scanned = make_scanned_training(meta["scanned_training"])
    stims = jnp.asarray([1, 2, 0, 1], jnp.int32)
    state, _ = scanned(init(jax.random.PRNGKey(1)), stims)
    assert scanned._cache_size() == 1
    obs_trace.summary(state.tele)                     # emit a report...
    obs_report.build_report("t", telemetry=obs_trace.summary(state.tele))
    state, _ = scanned(init(jax.random.PRNGKey(2)), stims)  # ...run again
    assert scanned._cache_size() == 1                 # no retrace
    obs_trace.summary(state.tele)


# ---------------------------------------------------------------------------
# Phase timing
# ---------------------------------------------------------------------------

def test_phase_timer_spans():
    t = obs_timing.PhaseTimer()
    with t.span("a") as mark:
        mark(jnp.ones(4) * 2)
    t.time_fn("b", lambda x: x + 1, jnp.ones(3), iters=2)
    s = t.summary()
    assert s["a"]["count"] == 1 and s["b"]["count"] == 2
    assert s["b"]["best_us"] <= s["b"]["mean_us"] + 1e-9


def test_profiler_trace_noop():
    with obs_timing.profiler_trace(None):
        pass


# ---------------------------------------------------------------------------
# Run report
# ---------------------------------------------------------------------------

def test_report_roundtrip(tmp_path):
    out, _, _ = run_training(n_trials=3, seed=0, telemetry=True)
    rep = obs_report.build_report(
        "unit", telemetry=out["telemetry"],
        timings={"total": dict(count=1, total_us=5.0, mean_us=5.0,
                               best_us=5.0)},
        cache=obs_timing.cache_snapshot(),
        config=dict(n_trials=3))
    assert rep["telemetry"]["out_spikes"] > 0
    assert rep["git_sha"]
    md = obs_report.to_markdown(rep)
    assert "out_spikes" in md and "Phase timings" in md
    paths = obs_report.write_report(rep, str(tmp_path / "r.json"))
    import json
    j = json.load(open(paths["json"]))
    assert j["telemetry"]["trials"] == 3
    assert os.path.exists(paths["md"])


def test_report_warnings_derived():
    tele = dict(overflow_fallbacks=2, census_events_max=999,
                vm_sat_hits=7)
    rep = obs_report.build_report("w", telemetry=tele,
                                  cache=dict(hits=0, misses=100,
                                             evictions=36, size=64,
                                             max_size=64))
    assert len(rep["warnings"]) == 3
    joined = " ".join(rep["warnings"])
    assert "overflow" in joined and "saturation" in joined \
        and "eviction storm" in joined


# ---------------------------------------------------------------------------
# First-divergence locator
# ---------------------------------------------------------------------------

def _mk_trace():
    return [(64, "SPIKES", np.zeros((64, 8))),
            (64, "RATES", np.arange(8.0)),
            (64, "WEIGHTS", np.ones((4, 8)))]


def test_first_divergence_none_on_match():
    assert first_divergence(_mk_trace(), _mk_trace()) is None


def test_first_divergence_localizes():
    a, b = _mk_trace(), _mk_trace()
    b[0][2][13, 5] = 1.0
    d = first_divergence(a, b)
    assert isinstance(d, Divergence)
    assert d.record == 0 and d.kind == "SPIKES"
    assert d.phase == "neuron-scan"
    assert d.where == (13, 5)
    assert d.step == 64 - 64 + 13           # absolute timestep
    assert d.n_mismatch == 1 and d.max_abs == pytest.approx(1.0)
    assert "index (13, 5)" in d.describe()


def test_first_divergence_structural():
    a, b = _mk_trace(), _mk_trace()
    b[2] = (64, "WEIGHTS", np.ones((4, 9)))
    d = first_divergence(a, b)
    assert d.structural and d.record == 2 and "shape" in d.detail

    d = first_divergence(_mk_trace(), _mk_trace()[:2])
    assert d.structural and "length" in d.detail

    b = _mk_trace()
    b[1] = (64, "CORR", b[1][2])
    d = first_divergence(_mk_trace(), b)
    assert d.structural and d.record == 1


def test_compare_traces_enriched_and_playback_telemetry():
    cfg = BSS2.reduced()
    rng = np.random.default_rng(0)
    T = 48
    ev = (rng.random((T, cfg.n_rows)) < 0.05).astype(np.float32)
    w = rng.integers(0, 40, (cfg.n_rows, cfg.n_cols)).astype(np.int8)
    prog = [pb.write_weights(w), pb.inject(ev), pb.run(16),
            pb.read_rates(), pb.write_ppu_program(programs.stdp_program()),
            pb.ppu_run(), pb.read_weights()]
    fb = pb.FastBackend(cfg, telemetry=True)
    trace = fb.execute(prog)
    s = fb.telemetry_summary()
    assert s["steps"] == T + 16
    assert s["in_events"] == int(ev.sum())
    assert s["vm_runs"] == 1 and s["trials"] == 1

    fb_off = pb.FastBackend(cfg)
    trace_off = fb_off.execute(prog)
    assert pb.compare_traces(trace, trace_off) == []

    bad = [(t, k, np.array(v, copy=True)) for t, k, v in trace_off]
    bad[-1][2].flat[3] += 5
    errs = pb.compare_traces(trace, bad)
    assert errs and "phase ppu" in errs[0] and "index" in errs[0]


# ---------------------------------------------------------------------------
# Specializer-cache accounting
# ---------------------------------------------------------------------------

def test_cache_evictions_counted_and_storm_detected():
    specialize.cache_clear()
    cap = specialize._CACHE_MAX
    with obs_timing.CacheDelta(warn=False) as cd:
        for i in range(cap + 8):
            # distinct 1-instruction programs; jit closures are lazy, so
            # nothing compiles — only the cache bookkeeping runs
            specialize.specialized_callable(
                np.asarray([isa.encode(isa.SPLAT, 0, 0, i)],
                           np.int64))
    assert cd.delta["misses"] == cap + 8
    assert cd.delta["evictions"] == 8
    assert cd.delta["size"] == cap
    assert obs_timing.eviction_storm(cd.delta)

    specialize.cache_clear()
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        with obs_timing.CacheDelta():
            for i in range(cap + 1):
                specialize.specialized_callable(
                    np.asarray([isa.encode(isa.SPLAT, 0, 0, i)],
                               np.int64))
    assert any("eviction storm" in str(w.message) for w in rec)
    specialize.cache_clear()


def test_cache_hits_no_storm():
    specialize.cache_clear()
    words = np.asarray(programs.stdp_program(), np.int64)
    with obs_timing.CacheDelta() as cd:
        for _ in range(5):
            specialize.specialized_callable(words)
    assert cd.delta == dict(hits=4, misses=1, evictions=0, size=1,
                            max_size=specialize._CACHE_MAX)
    assert not obs_timing.eviction_storm(cd.delta)
    specialize.cache_clear()


def test_instance_prefix_counters():
    # counters are fleet-wide totals: a [2]-instance prefix doubles the
    # per-instance spike count in one run
    cfg = BSS2.reduced()
    inst = sample_instance(cfg, jax.random.PRNGKey(0), prefix=(2,))
    core = AnnCore(cfg, inst, kernel_impl=KERNEL_IMPL)
    state = core.init_state(prefix=(2,))
    state = state._replace(syn=state.syn._replace(
        weights=jnp.broadcast_to(
            jnp.full((cfg.n_rows, cfg.n_cols), 45, jnp.int8),
            (2, cfg.n_rows, cfg.n_cols))))
    ev, ad = _events(64, cfg.n_rows, p=0.05)
    ev2 = jnp.broadcast_to(ev[:, None, :], (64, 2, cfg.n_rows))
    ad2 = jnp.broadcast_to(ad[:, None, :], (64, 2, cfg.n_rows))
    state, out = core.run(state, ev2, ad2,
                          telemetry=obs_trace.init_telemetry())
    s = obs_trace.summary(out["telemetry"])
    assert s["in_events"] == 2 * int(np.count_nonzero(np.asarray(ev)))
    assert s["out_spikes"] == int(np.asarray(out["spikes"]).sum())


# ---------------------------------------------------------------------------
# Layer scopes: every declared scope names ops of the compiled program, and
# the scopes write metadata only
# ---------------------------------------------------------------------------

def _scanned_text(wafer=None, scopes=True):
    """The compiled text of a tiny §5 experiment's scanned training. The
    density gate's work floor is lifted, so the program holds the census
    and both routes of the gate's ``lax.cond`` (the sparse route:
    packing, gather-matmul; the dense route: the dense matmul)."""
    ecfg = RSTDPConfig(n_inputs=16, n_neurons=16, pattern_size=5,
                       trial_steps=32)
    cfg = dataclasses.replace(BSS2.reduced(), n_rows=32, n_cols=16)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(synapse, "SPARSE_MIN_DENSE_WORK", 0)
        if not scopes:
            mp.setattr(obs_trace, "scope",
                       lambda name: contextlib.nullcontext())
        init, _, meta = make_experiment(
            cfg=cfg, ecfg=ecfg, instance_key=jax.random.PRNGKey(0),
            wafer=wafer)
        state = jax.eval_shape(init, jax.random.PRNGKey(1))
        stims = jax.ShapeDtypeStruct((3,), jnp.int32)
        return make_scanned_training(meta["scanned_training"]).lower(
            state, stims).compile().as_text()


def _without_metadata(hlo_text):
    """A compiled program's text without its metadata: each instruction's
    ``metadata={...}`` and the tables of source files and stack frames."""
    text = re.sub(r",?\s*metadata=\{[^}]*\}", "", hlo_text)
    out, table = [], False
    for line in text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            table = True
        elif table and (not line.strip()
                        or line.startswith(("%", "ENTRY", "HloModule"))):
            table = False
        if not table:
            out.append(line)
    return "\n".join(out)


@pytest.fixture(scope="module")
def scoped_texts():
    return dict(chip=_scanned_text(), wafer=_scanned_text(wafer=2))


def test_scopes_add_no_instruction(scoped_texts):
    """With the scopes as without them (``scope`` patched to a null
    context), the compiled programs differ only in their metadata."""
    plain = _scanned_text(scopes=False)
    assert plain != scoped_texts["chip"]
    assert _without_metadata(plain) == _without_metadata(
        scoped_texts["chip"])


@pytest.mark.parametrize("name", sorted(obs_trace.LAYER_SCOPES))
def test_layer_scope_names_compiled_ops(scoped_texts, name):
    """Each declared scope is a component of some op's ``op_name``: the
    router's in the tiny wafer, every other in the single chip."""
    text = scoped_texts["wafer" if name == "inter_chip_router" else "chip"]
    paths = re.findall(r'op_name="([^"]*)"', text)
    hits = [p for p in paths if name in p.split("/")]
    assert hits, name
    parent = obs_trace.LAYER_SCOPES[name]
    if parent is not None:
        assert all(parent in p.split("/")[:p.split("/").index(name)]
                   for p in hits), (name, hits[:3])


def test_scope_rejects_undeclared_name():
    with pytest.raises(ValueError, match="undeclared"):
        obs_trace.scope("synapse")


def test_fold_devices_sums_maxima_and_windows():
    """Per-device counters of one sharded window fold into the fleet's:
    per-chip totals add up, maxima take the largest, and window counts
    (each device runs every window) take the largest device's count."""
    tele = obs_trace.init_telemetry()._replace(
        in_events=jnp.int32(5), steps=jnp.int32(32),
        census_events_max=jnp.int32(9))
    zero = obs_trace.init_telemetry()
    parts = jax.tree.map(lambda *xs: jnp.stack(xs), zero._replace(
        in_events=jnp.int32(3), steps=jnp.int32(32),
        sparse_windows=jnp.int32(2), gated_windows=jnp.int32(2),
        census_events_max=jnp.int32(4)), zero._replace(
        in_events=jnp.int32(4), steps=jnp.int32(32),
        sparse_windows=jnp.int32(2), gated_windows=jnp.int32(2),
        census_events_max=jnp.int32(11)))
    s = obs_trace.summary(obs_trace.fold_devices(tele, parts))
    assert (s["in_events"], s["steps"], s["sparse_windows"],
            s["gated_windows"], s["census_events_max"]) == (12, 64, 2, 2,
                                                            11)
    assert obs_trace.fold_devices(None, parts) is None
