"""Event-sparse synaptic path: packing round-trip + BIT-exact equivalence.

The sparse path (``repro.core.events`` + ``repro.kernels.synray_sparse``)
claims bit-identity with the dense matmul and the per-step oracle — not
tolerance-equality — for any window that fits its static capacities. The
claim rests on XLA:CPU's in-order FMA reduction chain (see
synray_sparse/ref.py), so this suite asserts ``assert_array_equal``
across a 0%..100% density sweep, through both the jnp ref and the kernel
in interpret mode, with float STP-like efficacies, multi-address streams,
and instance prefixes.

The flip side of the static capacities is the overflow contract: a FORCED
sparse path with an undersized capacity silently drops events and must
provably diverge from the dense result (the divergence-contract pattern
of test_fused.py's const_addr test), while ``sparse="auto"`` detects the
same overflow at runtime and falls back to dense — never wrong numbers.

``ANNCORE_KERNEL_IMPL`` (default "auto") forces the kernel impl for the
core-level classes — the tier-2 CI job sets "interpret" to run the suite
through the actual Pallas kernels.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.bss2 import BSS2
from repro.core import events, synapse
from repro.core.anncore import AnnCore
from repro.kernels.synray_sparse import ops as sparse_ops
from repro.verif.mismatch import sample_instance

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

KERNEL_IMPL = os.environ.get("ANNCORE_KERNEL_IMPL", "auto")
DENSITIES = [0.0, 0.001, 0.01, 0.1, 0.5, 1.0]


def _window(T, R, key=0, p=0.1, n_addr=4):
    """[T, R] events with STP-like float efficacies (0 = silent)."""
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    fired = jax.random.uniform(ks[0], (T, R)) < p
    eff = jax.random.uniform(ks[1], (T, R), minval=0.1, maxval=1.5)
    ev = jnp.where(fired, eff, 0.0)
    ad = jax.random.randint(ks[2], (T, R), 0, n_addr, jnp.int8)
    return ev, ad


def _array(R, C, key=1, n_addr=4):
    ks = jax.random.split(jax.random.PRNGKey(key), 2)
    w = jax.random.randint(ks[0], (R, C), 0, 64, jnp.int8)
    a = jax.random.randint(ks[1], (R, C), 0, n_addr, jnp.int8)
    return w, a


def _round_trip(ev, ad, max_events):
    T, R = ev.shape
    stream = events.pack_events(ev, ad, max_events)
    ev2, ad2 = events.unpack_events(stream, T, R)
    return stream, ev2, ad2


class TestEventStreamRoundTrip:
    @pytest.mark.parametrize("p", DENSITIES)
    def test_round_trip_exact(self, p):
        """pack -> unpack reproduces the window exactly: efficacies
        everywhere, addresses at fired slots (silent slots carry 0 — the
        stream only transports addresses WITH events)."""
        T, R = 40, 24
        ev, ad = _window(T, R, key=3, p=p)
        _, ev2, ad2 = _round_trip(ev, ad, T * R)
        np.testing.assert_array_equal(np.asarray(ev), np.asarray(ev2))
        fired = np.asarray(ev) != 0
        np.testing.assert_array_equal(np.asarray(ad) * fired,
                                      np.asarray(ad2))
        assert (np.asarray(ad2) * ~fired == 0).all()

    def test_t_major_order_and_census(self):
        ev, ad = _window(48, 16, key=4, p=0.2)
        stream = events.pack_events(ev, ad, 48 * 16)
        n = int(stream.n_events)
        assert n == int(np.count_nonzero(np.asarray(ev)))
        assert np.asarray(stream.valid).sum() == n
        t = np.asarray(stream.t)[:n]
        row = np.asarray(stream.row)[:n]
        assert (np.diff(t) >= 0).all(), "records must be t-major"
        same_t = np.diff(t) == 0
        assert (np.diff(row)[same_t] > 0).all(), \
            "rows must ascend within a step"

    def test_overflow_reports_true_count(self):
        """Over-capacity packing keeps the TRUE census (the auto-switch
        predicate) while the stored records stay a valid prefix."""
        ev, ad = _window(32, 32, key=5, p=0.5)
        n_true = int(np.count_nonzero(np.asarray(ev)))
        cap = n_true // 3
        stream = events.pack_events(ev, ad, cap)
        assert int(stream.n_events) == n_true
        assert bool(events.overflowed(stream))
        assert np.asarray(stream.valid).sum() == cap
        full = events.pack_events(ev, ad, 32 * 32)
        np.testing.assert_array_equal(np.asarray(stream.eff),
                                      np.asarray(full.eff)[:cap])

    def test_regroup_matches_stream(self):
        """[T, K] regrouping holds exactly the stream's records, in
        stream (row-ascending) order per step."""
        T, R = 40, 24
        ev, ad = _window(T, R, key=6, p=0.15)
        stream = events.pack_events(ev, ad, T * R)
        rows_tk, addr_tk, eff_tk = events.regroup_events(stream, T, R)
        evn, adn = np.asarray(ev), np.asarray(ad)
        for t in range(T):
            rr = np.nonzero(evn[t])[0]
            k = len(rr)
            np.testing.assert_array_equal(np.asarray(rows_tk)[t, :k], rr)
            np.testing.assert_array_equal(np.asarray(eff_tk)[t, :k],
                                          evn[t, rr])
            np.testing.assert_array_equal(np.asarray(addr_tk)[t, :k],
                                          adn[t, rr])
            assert (np.asarray(eff_tk)[t, k:] == 0).all()

    def test_window_stats(self):
        """The auto-switch census: worst per-instance total and worst
        single-step count, across an instance prefix."""
        ev = jnp.zeros((4, 2, 8)).at[0, 0, :3].set(1.0).at[2, 1, :5].set(
            0.7).at[3, 1, 0].set(0.2)
        n, kmax = events.window_stats(ev)
        assert int(n) == 6 and int(kmax) == 5

    if HAVE_HYPOTHESIS:
        @settings(max_examples=40, deadline=None)
        @given(seed=st.integers(0, 2**31 - 1),
               t_len=st.integers(1, 24), rows=st.integers(1, 24),
               density=st.floats(0.0, 1.0))
        def test_round_trip_hypothesis(self, seed, t_len, rows, density):
            """Property: ANY window round-trips through the stream."""
            rng = np.random.RandomState(seed)
            ev = jnp.asarray(
                np.where(rng.rand(t_len, rows) < density,
                         rng.rand(t_len, rows).astype(np.float32) + 0.1,
                         0.0).astype(np.float32))
            ad = jnp.asarray(rng.randint(0, 64, (t_len, rows)), jnp.int8)
            _, ev2, ad2 = _round_trip(ev, ad, t_len * rows)
            np.testing.assert_array_equal(np.asarray(ev), np.asarray(ev2))
            fired = np.asarray(ev) != 0
            np.testing.assert_array_equal(np.asarray(ad) * fired,
                                          np.asarray(ad2))
    else:
        @pytest.mark.skip(reason="hypothesis not installed")
        def test_round_trip_hypothesis(self):
            pass


class TestSparseBitExact:
    """sparse == dense == per-step oracle, EXACT equality, 0%..100%.

    C = 512 keeps T * R * C above ``synapse.SPARSE_MIN_DENSE_WORK`` so
    the sparse="auto" tests exercise the runtime switch rather than the
    static small-window demotion to dense."""

    T, R, C = 64, 64, 512

    def _operands(self, p, key=0, n_addr=4):
        ev, ad = _window(self.T, self.R, key=key, p=p, n_addr=n_addr)
        w, a = _array(self.R, self.C, key=key + 1, n_addr=n_addr)
        gain = jax.random.uniform(jax.random.PRNGKey(key + 2), (self.C,),
                                  minval=0.5, maxval=1.5)
        return w, a, ev, ad, gain

    @pytest.mark.parametrize("impl", ["ref", "interpret"])
    @pytest.mark.parametrize("p", DENSITIES)
    def test_sweep_against_dense_and_oracle(self, p, impl):
        w, a, ev, ad, gain = self._operands(p, key=int(p * 1000))
        dense = synapse.synaptic_current_window(w, a, ev, ad, gain,
                                                sparse="never")
        sparse = synapse.synaptic_current_window(
            w, a, ev, ad, gain, impl=impl, sparse="always",
            max_events=self.T * self.R, k_cap=self.R)
        np.testing.assert_array_equal(np.asarray(sparse),
                                      np.asarray(dense))
        oracle = jnp.stack([synapse.synaptic_current(w, a, ev[t], ad[t],
                                                     gain)
                            for t in range(self.T)])
        np.testing.assert_array_equal(np.asarray(sparse),
                                      np.asarray(oracle))

    @pytest.mark.parametrize("impl", ["ref", "interpret"])
    def test_auto_fits_is_exact(self, impl):
        """Below-threshold window through sparse="auto" (the lax.cond
        picks the sparse branch) — still bit-identical to dense."""
        w, a, ev, ad, gain = self._operands(0.005, key=11)
        assert self.T * self.R * self.C >= synapse.SPARSE_MIN_DENSE_WORK
        dense = synapse.synaptic_current_window(w, a, ev, ad, gain,
                                                sparse="never")
        n, kmax = events.window_stats(ev)
        assert int(n) <= events.default_max_events(
            self.T, self.R, synapse.SPARSE_THRESHOLD)
        auto = jax.jit(lambda *o: synapse.synaptic_current_window(
            *o, impl=impl, sparse="auto"))(w, a, ev, ad, gain)
        np.testing.assert_array_equal(np.asarray(auto), np.asarray(dense))

    def test_const_addr_stream(self):
        """Row-constant addresses (the §5 wiring): sparse == the
        const_addr dense fast path, exactly."""
        w, a = _array(self.R, self.C, key=21)
        ev, _ = _window(self.T, self.R, key=20, p=0.02)
        row_addr = jax.random.randint(jax.random.PRNGKey(22), (self.R,),
                                      0, 4, jnp.int8)
        ad = jnp.broadcast_to(row_addr, ev.shape)
        fast = synapse.synaptic_current_window(w, a, ev, ad, 1.0,
                                               sparse="never",
                                               const_addr=True)
        sparse = synapse.synaptic_current_window(
            w, a, ev, ad, 1.0, sparse="always",
            max_events=self.T * self.R, k_cap=self.R)
        np.testing.assert_array_equal(np.asarray(sparse),
                                      np.asarray(fast))

    @pytest.mark.parametrize("impl", ["ref", "interpret"])
    def test_instance_prefix(self, impl):
        """A fleet prefix rides the sparse kernel's instance grid axis —
        still bit-identical per instance."""
        prefix, T, R, C = (3,), 48, 32, 64
        ks = jax.random.split(jax.random.PRNGKey(31), 5)
        fired = jax.random.uniform(ks[0], (T, *prefix, R)) < 0.03
        ev = jnp.where(fired,
                       jax.random.uniform(ks[1], (T, *prefix, R),
                                          minval=0.1, maxval=1.5), 0.0)
        ad = jax.random.randint(ks[2], (T, *prefix, R), 0, 4, jnp.int8)
        w = jax.random.randint(ks[3], (*prefix, R, C), 0, 64, jnp.int8)
        a = jax.random.randint(ks[4], (*prefix, R, C), 0, 4, jnp.int8)
        dense = synapse.synaptic_current_window(w, a, ev, ad, 1.0,
                                                sparse="never")
        sparse = synapse.synaptic_current_window(
            w, a, ev, ad, 1.0, impl=impl, sparse="always",
            max_events=T * R, k_cap=R)
        np.testing.assert_array_equal(np.asarray(sparse),
                                      np.asarray(dense))

    def test_small_window_static_dense_demotion(self):
        """Below the work floor, sparse="auto" compiles to the pure dense
        program — identical to sparse="never" for the same impl (e.g. the
        16 x 16 §5 experiment never pays any switch overhead)."""
        T, R, C = 32, 16, 32
        assert T * R * C < synapse.SPARSE_MIN_DENSE_WORK
        ev, ad = _window(T, R, key=81, p=0.05)
        w, a = _array(R, C, key=82)
        for impl in ("ref", "interpret"):
            auto = synapse.synaptic_current_window(
                w, a, ev, ad, 1.0, impl=impl, sparse="auto")
            never = synapse.synaptic_current_window(
                w, a, ev, ad, 1.0, impl=impl, sparse="never")
            np.testing.assert_array_equal(np.asarray(auto),
                                          np.asarray(never))

    def test_ops_ref_vs_interpret(self):
        """The kernel itself against its jnp ref on the same regrouped
        records — the kernel preserves the reduction chain bit-for-bit."""
        T, R, C = 32, 64, 128
        ev, ad = _window(T, R, key=41, p=0.1)
        w, a = _array(R, C, key=42)
        stream = events.pack_events(ev, ad, T * R)
        recs = events.regroup_events(stream, T, 16)
        outs = [sparse_ops.sparse_window(*recs, w, a, impl=impl)
                for impl in ("ref", "interpret")]
        np.testing.assert_array_equal(np.asarray(outs[0]),
                                      np.asarray(outs[1]))


class TestPackRegrouped:
    """The sparse route's scatter-free packing against the stream form it
    replaces: the same [T, K] records bit for bit, including windows that
    overflow either capacity (the divergence contract below depends on
    which records are dropped)."""

    T, R = 128, 64

    @staticmethod
    def _stream_form(ev, ad, max_events, k_cap):
        T = ev.shape[-2]

        def one(e, a):
            return events.regroup_events(
                events.pack_events(e, a, max_events), T, k_cap)

        return one(ev, ad) if ev.ndim == 2 else jax.vmap(one)(ev, ad)

    @pytest.mark.parametrize("prefix", [(), (3,)], ids=["single", "n3"])
    @pytest.mark.parametrize("caps", [(656, 16), (64, 4), (T * R, R)],
                             ids=["s5", "tight", "lossless"])
    @pytest.mark.parametrize("p", DENSITIES)
    def test_bit_identical_to_stream_form(self, p, caps, prefix):
        max_events, k_cap = caps
        shape = (*prefix, self.T, self.R)
        ks = jax.random.split(jax.random.PRNGKey(int(p * 1000) + k_cap), 3)
        fired = jax.random.uniform(ks[0], shape) < p
        ev = jnp.where(fired, jax.random.uniform(ks[1], shape, minval=0.1,
                                                 maxval=1.5), 0.0)
        ad = jax.random.randint(ks[2], shape, 0, 64, jnp.int8)
        want = self._stream_form(ev, ad, max_events, k_cap)
        got = jax.jit(events.pack_regrouped, static_argnums=(2, 3))(
            ev, ad, max_events, k_cap)
        for name, w, g in zip(("rows", "addr", "eff"), want, got):
            w, g = np.asarray(w), np.asarray(g)
            assert g.dtype == w.dtype and g.shape == w.shape, name
            if name == "eff":
                w, g = w.view(np.uint32), g.view(np.uint32)
            np.testing.assert_array_equal(g, w, err_msg=name)

    def test_sparse_route_lowers_without_scatter(self):
        """TPU scatters serialize: the sparse route's packing must lower
        with none (the stream form it replaced lowers with several)."""
        ev = jax.ShapeDtypeStruct((2, 256, 128), jnp.float32)
        ad = jax.ShapeDtypeStruct((2, 256, 128), jnp.int8)
        text = sparse_ops._pack_regroup.lower(
            ev, ad, max_events=656, k_cap=16).as_text()
        assert "scatter" not in text
        stream = jax.jit(lambda e, a: self._stream_form(e, a, 656, 16))
        assert "scatter" in stream.lower(ev, ad).as_text()


class TestOverflowContract:
    """Undersized capacities must never produce silently wrong numbers.
    (Sized above ``SPARSE_MIN_DENSE_WORK`` so the "auto" cases reach the
    runtime census rather than the static dense demotion.)"""

    T, R, C = 64, 64, 512

    def _operands(self):
        ev, ad = _window(self.T, self.R, key=51, p=0.5)
        w, a = _array(self.R, self.C, key=52)
        return w, a, ev, ad

    def test_forced_sparse_overflow_diverges(self):
        """The divergence contract: forcing sparse with a deliberately
        undersized stream capacity DROPS events, provably diverging from
        dense — the broken promise the auto fallback exists to prevent."""
        w, a, ev, ad = self._operands()
        dense = synapse.synaptic_current_window(w, a, ev, ad, 1.0,
                                                sparse="never")
        n = int(np.count_nonzero(np.asarray(ev)))
        forced = synapse.synaptic_current_window(
            w, a, ev, ad, 1.0, sparse="always", max_events=n // 4,
            k_cap=self.R)
        assert np.abs(np.asarray(forced) - np.asarray(dense)).max() > 0, \
            "undersized capacity without fallback must be detectable"

    def test_auto_overflow_falls_back_dense(self):
        """Same undersized capacity through sparse="auto": the runtime
        census detects the overflow and the window runs dense — exact."""
        w, a, ev, ad = self._operands()
        dense = synapse.synaptic_current_window(w, a, ev, ad, 1.0,
                                                sparse="never")
        n = int(np.count_nonzero(np.asarray(ev)))
        auto = jax.jit(lambda *o: synapse.synaptic_current_window(
            *o, sparse="auto", max_events=n // 4))(w, a, ev, ad, 1.0)
        np.testing.assert_array_equal(np.asarray(auto), np.asarray(dense))

    def test_auto_per_step_overflow_falls_back(self):
        """k_cap (per-step records) undersized: auto must fall back even
        when the TOTAL census fits."""
        w, a, ev, ad = self._operands()
        dense = synapse.synaptic_current_window(w, a, ev, ad, 1.0,
                                                sparse="never")
        auto = synapse.synaptic_current_window(
            w, a, ev, ad, 1.0, sparse="auto",
            max_events=self.T * self.R, k_cap=2)
        np.testing.assert_array_equal(np.asarray(auto), np.asarray(dense))
        forced = synapse.synaptic_current_window(
            w, a, ev, ad, 1.0, sparse="always",
            max_events=self.T * self.R, k_cap=2)
        assert np.abs(np.asarray(forced) - np.asarray(dense)).max() > 0

    def test_step_overflow_predicate_flags_silent_regime(self):
        """The latent-bug regime: a stream that FITS its total capacity
        (``overflowed() == False``) but holds a step with more than
        ``k_cap`` records — ``regroup_events`` drops that step's tail
        while the total-capacity predicate reports all-clear. The
        per-step predicate ``step_overflowed`` must flag it, and the
        shared ``census_fits`` gate (what sparse="auto" and the wafer
        router's link budget both use) must refuse it."""
        w, a, ev, ad = self._operands()
        dense = synapse.synaptic_current_window(w, a, ev, ad, 1.0,
                                                sparse="never")
        k_cap = 2
        stream = events.pack_events(ev, ad, self.T * self.R)
        assert not bool(events.overflowed(stream)), \
            "regime needs a stream that fits its total capacity"
        assert bool(events.step_overflowed(stream, self.T, k_cap)), \
            "per-step predicate must flag the regroup drop"
        n, kmax = events.window_stats(ev)
        assert not bool(events.census_fits(n, kmax, self.T * self.R,
                                           k_cap)), \
            "the shared gate must refuse what regroup would drop"
        # and the drop is real: the forced path diverges from dense
        forced = synapse.synaptic_current_window(
            w, a, ev, ad, 1.0, sparse="always",
            max_events=self.T * self.R, k_cap=k_cap)
        assert np.abs(np.asarray(forced) - np.asarray(dense)).max() > 0

    def test_step_counts_and_truncate_stream(self):
        """``step_counts`` reports the stored per-step records;
        ``truncate_stream`` cuts each step at the budget while keeping
        ``n_events`` at the TRUE count (drop-detectable)."""
        ev, ad = _window(16, 32, key=53, p=0.4)
        T = 16
        stream = events.pack_events(ev, ad, T * 32)
        counts = np.asarray(events.step_counts(stream, T))
        np.testing.assert_array_equal(
            counts, np.count_nonzero(np.asarray(ev), axis=1))
        cut = events.truncate_stream(stream, T, 3)
        cut_counts = np.asarray(events.step_counts(cut, T))
        np.testing.assert_array_equal(cut_counts,
                                      np.minimum(counts, 3))
        # kept records are exactly each step's first 3 (t-major order)
        ev2, _ = events.unpack_events(cut, T, 32)
        kept = np.asarray(ev).copy()
        for t in range(T):
            nz = np.nonzero(kept[t])[0]
            kept[t, nz[3:]] = 0.0
        np.testing.assert_array_equal(np.asarray(ev2), kept)
        assert int(cut.n_events) == int(stream.n_events)
        assert bool(events.step_overflowed(cut, T, 3)) == bool(
            (counts > 3).any())


class TestAutoGate:
    """The const_addr-aware auto gate (PR 6 follow-on): with const_addr
    the dense side is the once-resolved plain matmul, so the crossover
    drops — "auto" sizes its capacities from the lower
    ``SPARSE_THRESHOLD_CONST_ADDR`` and hands the intermediate-density
    band back to dense. Each route is internally bit-exact; across the
    two dense variants (masked vs once-resolved matmul) the house
    const_addr tolerance applies (see tests/test_fused.py)."""

    T, R, C = 128, 128, 256   # T*R*C = 4M >= SPARSE_MIN_DENSE_WORK

    def _operands(self, p):
        # const_addr-compatible stream: one address per row, constant
        # over the window (the mapper's address-schedule regime)
        ks = jax.random.split(jax.random.PRNGKey(71), 4)
        row_addr = jax.random.randint(ks[0], (self.R,), 0, 64, jnp.int8)
        fired = jax.random.uniform(ks[1], (self.T, self.R)) < p
        eff = jax.random.uniform(ks[2], (self.T, self.R), minval=0.1,
                                 maxval=1.5)
        ev = jnp.where(fired, eff, 0.0)
        ad = jnp.broadcast_to(row_addr, (self.T, self.R))
        w = jax.random.randint(ks[3], (self.R, self.C), 0, 64, jnp.int8)
        a = jnp.broadcast_to(row_addr[:, None], (self.R, self.C))
        return w, a, ev, ad

    def test_const_addr_lowers_crossover(self):
        """At a density between the two thresholds (0.02 < p <= 0.05)
        the generic gate still routes sparse, the const_addr gate picks
        dense — where the once-resolved matmul wins."""
        from repro.obs import trace as obs_trace
        w, a, ev, ad = self._operands(p=0.03)
        n, kmax = events.window_stats(ev)
        assert (synapse.SPARSE_THRESHOLD_CONST_ADDR * self.T * self.R
                < int(n) <= synapse.SPARSE_THRESHOLD * self.T * self.R), \
            "regime check: density must sit between the two thresholds"

        # the CPU's crossover: the kernels' gate ignores the promise
        # (test_kernels_gate_ignores_promise)
        def run(const_addr):
            return synapse.synaptic_current_window(
                w, a, ev, ad, 1.0, impl="ref", const_addr=const_addr,
                sparse="auto", telemetry=obs_trace.init_telemetry())

        i_gen, tl_gen = jax.jit(lambda: run(False))()
        i_ca, tl_ca = jax.jit(lambda: run(True))()
        assert int(tl_gen.sparse_windows) == 1, \
            "generic gate must still route this window sparse"
        assert int(tl_ca.dense_windows) == 1, \
            "const_addr gate must hand the window back to dense"
        assert int(tl_ca.overflow_fallbacks) == 1
        # across routes the result agrees to the const_addr fast-path
        # tolerance (the once-resolved matmul reduces in a different
        # order than the masked path — same contract as test_fused.py's
        # const_addr coverage; within one configured route the program
        # is fixed, so repeated runs stay bit-identical)
        np.testing.assert_allclose(np.asarray(i_gen), np.asarray(i_ca),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("impl,const_addr,thr", [
        ("ref", True, synapse.SPARSE_THRESHOLD_CONST_ADDR),
        ("ref", False, synapse.SPARSE_THRESHOLD),
        ("interpret", True, synapse.SPARSE_THRESHOLD_CONST_ADDR),
        ("interpret", False, synapse.SPARSE_THRESHOLD_CONST_ADDR),
        ("pallas", False, synapse.SPARSE_THRESHOLD_CONST_ADDR)])
    def test_kernels_gate_ignores_promise(self, impl, const_addr, thr):
        """The default threshold follows the dense window the gate falls
        back to: only the CPU's broadcasting oracle takes the higher one;
        the kernels match addresses per step, and size their gate alike
        with or without the promise."""
        assert synapse.default_sparse_threshold(impl, const_addr) == thr

    def test_kernels_gate_routes_alike_without_promise(self):
        """At a density between the two thresholds the interpreted
        kernels hand the window back to dense with or without the
        promise, bit-identically."""
        from repro.obs import trace as obs_trace
        w, a, ev, ad = self._operands(p=0.03)

        def run(const_addr):
            return synapse.synaptic_current_window(
                w, a, ev, ad, 1.0, impl="interpret", const_addr=const_addr,
                sparse="auto", telemetry=obs_trace.init_telemetry())

        i_gen, tl_gen = jax.jit(lambda: run(False))()
        i_ca, tl_ca = jax.jit(lambda: run(True))()
        assert int(tl_gen.overflow_fallbacks) == 1
        assert int(tl_ca.overflow_fallbacks) == 1
        np.testing.assert_array_equal(np.asarray(i_gen), np.asarray(i_ca))

    def test_explicit_threshold_still_wins(self):
        """A caller-provided sparse_threshold overrides the const_addr
        default (no behavior change for explicit configurations)."""
        from repro.obs import trace as obs_trace
        w, a, ev, ad = self._operands(p=0.03)
        i, tl = synapse.synaptic_current_window(
            w, a, ev, ad, 1.0, impl=KERNEL_IMPL, const_addr=True,
            sparse="auto", sparse_threshold=synapse.SPARSE_THRESHOLD,
            telemetry=obs_trace.init_telemetry())
        assert int(tl.sparse_windows) == 1


class TestDenseBatchBlock:
    """Satellite: the dense kernel's batch-block pick. The old
    ``next(d for d in (8, 4, 2, 1) if T % d == 0)`` silently degraded to
    bb=1 for odd T; now T pads up to the block and slices back."""

    R, C = 16, 16

    def _operands(self, T, key=61):
        ev, ad = _window(T, self.R, key=key, p=0.2)
        w, a = _array(self.R, self.C, key=key + 1)
        return w, a, ev, ad

    @pytest.mark.parametrize("T", [97, 101, 50])
    def test_prime_and_odd_T_through_kernel(self, T):
        """Mirrors test_blocked's T % block != 0 cases: the padded kernel
        path stays exact for window lengths the block does not divide."""
        w, a, ev, ad = self._operands(T)
        ref = synapse.synaptic_current_window(w, a, ev, ad, 1.0,
                                              impl="ref", sparse="never")
        out = synapse.synaptic_current_window(w, a, ev, ad, 1.0,
                                              impl="interpret",
                                              sparse="never")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("bb", [5, 16])
    def test_bb_override_knob(self, bb):
        """The bb= override reaches the kernel (incl. bb > T and bb not
        dividing T) without changing results."""
        T = 13
        w, a, ev, ad = self._operands(T, key=63)
        ref = synapse.synaptic_current_window(w, a, ev, ad, 1.0,
                                              impl="ref", sparse="never")
        out = synapse.synaptic_current_window(w, a, ev, ad, 1.0,
                                              impl="interpret",
                                              sparse="never", bb=bb)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


class TestAnnCoreSparse:
    """The sparse path wired into the fused backend: whole-run equality."""

    CFG = dataclasses.replace(BSS2.reduced(), n_rows=16, n_cols=16)

    def _cores(self, **kw):
        inst = sample_instance(self.CFG, jax.random.PRNGKey(0), ())
        dense = AnnCore(self.CFG, inst, backend="fused",
                        kernel_impl=KERNEL_IMPL, sparse_mode="never")
        sparse = AnnCore(self.CFG, inst, backend="fused",
                         kernel_impl=KERNEL_IMPL, sparse_mode="always",
                         sparse_max_events=200 * 8, sparse_k_cap=8, **kw)
        oracle = AnnCore(self.CFG, inst, backend="oracle")
        st = oracle.init_state(())
        kw_, ka = jax.random.split(jax.random.PRNGKey(9))
        st = st._replace(syn=st.syn._replace(
            weights=jax.random.randint(
                kw_, (self.CFG.n_rows, self.CFG.n_cols), 20, 64, jnp.int8),
            addresses=jax.random.randint(
                ka, (self.CFG.n_rows, self.CFG.n_cols), 0, 4, jnp.int8)))
        return oracle, dense, sparse, st

    def test_fused_sparse_bit_identical_to_dense(self):
        """sparse_mode="always" vs "never" on the same fused core: the
        whole run (spikes AND final state) is bit-identical."""
        oracle, dense, sparse, st = self._cores()
        ks = jax.random.split(jax.random.PRNGKey(71))
        ev = (jax.random.uniform(ks[0], (200, self.CFG.n_rows)) < 0.1
              ).astype(jnp.float32)
        ad = jax.random.randint(ks[1], (200, self.CFG.n_rows), 0, 4,
                                jnp.int8)
        s1, o1 = jax.jit(dense.run)(st, ev, ad)
        s2, o2 = jax.jit(sparse.run)(st, ev, ad)
        assert float(o1["spikes"].sum()) > 0
        np.testing.assert_array_equal(np.asarray(o1["spikes"]),
                                      np.asarray(o2["spikes"]))
        for x, y in zip(jax.tree.leaves(s1), jax.tree.leaves(s2)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        _, o3 = jax.jit(oracle.run)(st, ev, ad)
        np.testing.assert_allclose(np.asarray(o3["spikes"]),
                                   np.asarray(o2["spikes"]),
                                   rtol=1e-4, atol=1e-4)

    def test_sparse_threads_through_run_training(self):
        """The sparse knobs reach the core through make_experiment /
        run_training, and the §5 experiment result is invariant."""
        from repro.core.hybrid import RSTDPConfig, run_training
        ecfg = RSTDPConfig(trial_steps=96)
        o1, _, meta = run_training(n_trials=5, seed=7, ecfg=ecfg,
                                   sparse_mode="never")
        assert meta["core"].sparse_mode == "never"
        o2, _, meta2 = run_training(n_trials=5, seed=7, ecfg=ecfg,
                                    sparse_mode="auto",
                                    sparse_threshold=0.05)
        assert meta2["core"].sparse_mode == "auto"
        assert meta2["core"].sparse_threshold == 0.05
        np.testing.assert_array_equal(o1["w_signed_final"],
                                      o2["w_signed_final"])
        np.testing.assert_array_equal(o1["reward"], o2["reward"])
