"""Wafer-scale multi-chip contracts (``repro.wafer``).

The correctness anchor is split-vs-monolithic bit-equality: a K-chip
wafer run and the single-big-chip run with block-diagonal weights (and
the same routes in global coordinates) must agree with
``assert_array_equal`` — off-block weights are exact-zero FMA terms, and
the router's scatter-max merge is order-independent. The link-budget
contract mirrors the sparse synaptic path: "auto" falls back bit-exactly
and counts, forced "compact" over budget visibly diverges and counts —
overflow is never silent.
"""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.bss2 import BSS2
from repro.core import hybrid
from repro.core.anncore import AnnCore
from repro.obs import trace as obs_trace
from repro.verif.mismatch import sample_instance
from repro.wafer import (InterChipRouter, WaferTopology, make_plan,
                         monolithic_plan, monolithic_weights, run_windows,
                         s5_column_plan)

R, C, T, W = 16, 8, 32, 3
ADDR = 7   # every test route delivers address 7; relay synapses match it


def _random_plan(K, kind, rng, per_link=4):
    """Random routes on every link of the topology (addr 7 throughout,
    so dst-row address uniqueness holds trivially)."""
    routes = []
    for s in range(K):
        dsts = [(s + 1) % K] if kind == "ring" else list(range(K))
        for d in dsts:
            for _ in range(per_link):
                routes.append((s, int(rng.integers(C)), d,
                               int(rng.integers(R)), ADDR))
    return make_plan(WaferTopology(K, kind), R, C, routes)


def _chip_arrays(plan, rng):
    """Per-chip weight/address planes; relay rows store address 7 so the
    routed events conduct synaptic current (the route must matter)."""
    K = plan.topology.n_chips
    w = rng.integers(20, 60, (K, R, C)).astype(np.int8)
    a = np.zeros((K, R, C), np.int8)
    relay = plan.relay_rows()
    for k in range(K):
        a[k][relay[k]] = ADDR
    return w, a


def _window_inputs(K, rng, p=0.3):
    ev = (rng.random((W, T, K, R)) < p).astype(np.float32)
    ad = np.zeros((W, T, K, R), np.int8)
    return ev, ad


def _split_core(K, backend):
    cfg = dataclasses.replace(BSS2.reduced(), n_rows=R, n_cols=C)
    inst = sample_instance(cfg, jax.random.PRNGKey(3), (K,))
    return AnnCore(cfg, inst, backend=backend), inst, cfg


def _mono_core(inst, cfg, K, backend):
    """The same sampled instance as ONE chip: chip-block-contiguous
    columns (global col = chip * C + col) and rows broadcast per chip —
    exactly the layout ``monolithic_plan`` uses."""
    minst = dict(
        neuron_params={k: v.reshape(1, -1)
                       for k, v in inst["neuron_params"].items()},
        weight_gain=inst["weight_gain"].reshape(1, -1),
        stp_offset=inst["stp_offset"].reshape(1, -1),
        stp_calib=inst["stp_calib"].reshape(1, -1),
        cadc_offset=inst["cadc_offset"].reshape(1, -1),
        cadc_gain=inst["cadc_gain"].reshape(1, -1))
    mcfg = dataclasses.replace(cfg, n_rows=K * R, n_cols=K * C)
    return AnnCore(mcfg, minst, backend=backend), mcfg


def _run(core, router, prefix, w, a, ev, ad, telemetry=False):
    st = core.init_state(prefix)
    st = st._replace(syn=st.syn._replace(weights=jnp.asarray(w),
                                         addresses=jnp.asarray(a)))
    tele = obs_trace.init_telemetry() if telemetry else None
    _, out = jax.jit(lambda s, e, d: run_windows(
        core, router, s, e, d, telemetry=tele))(
            st, jnp.asarray(ev), jnp.asarray(ad))
    return out


def _counters(out):
    tl = out["telemetry"]
    return {k: int(np.asarray(getattr(tl, k)))
            for k in ("routed_events", "link_overflows", "link_events_max")}


class TestTopology:
    def test_links_and_uniform_out_degree(self):
        ring = WaferTopology(4, "ring")
        assert ring.links() == ((0, 1), (1, 2), (2, 3), (3, 0))
        assert ring.links_per_chip == 1
        a2a = WaferTopology(3, "all2all")
        assert len(a2a.links()) == 9 and (0, 0) in a2a.links()
        assert a2a.links_per_chip == 3
        # K == 1 ring degenerates to the single self-link
        assert WaferTopology(1, "ring").links() == ((0, 0),)

    def test_plan_validation(self):
        topo = WaferTopology(2, "ring")
        with pytest.raises(AssertionError, match="non-links"):
            make_plan(topo, R, C, [(0, 0, 0, 0, 1)])   # self-link not in ring
        with pytest.raises(AssertionError, match="6-bit"):
            make_plan(topo, R, C, [(0, 0, 1, 0, 64)])
        with pytest.raises(AssertionError, match="conflicting"):
            make_plan(topo, R, C, [(0, 0, 1, 3, 1), (0, 1, 1, 3, 2)])

    def test_monolithic_embedding(self):
        rng = np.random.default_rng(0)
        plan = _random_plan(2, "ring", rng)
        mono = monolithic_plan(plan)
        assert mono.topology.n_chips == 1
        assert mono.n_rows == 2 * R and mono.n_cols == 2 * C
        np.testing.assert_array_equal(
            mono.dst_row, plan.dst_chip * R + plan.dst_row)
        w = rng.integers(0, 63, (2, R, C)).astype(np.int8)
        mw = monolithic_weights(w)
        np.testing.assert_array_equal(mw[:R, :C], w[0])
        np.testing.assert_array_equal(mw[R:, C:], w[1])
        assert (mw[:R, C:] == 0).all() and (mw[R:, :C] == 0).all()


class TestSplitVsMonolithic:
    """The tentpole contract: K chips + router == one big chip with
    block-diagonal weights, bit-for-bit, on both batch backends."""

    @pytest.mark.parametrize("kind,K", [("ring", 2), ("all2all", 4)])
    @pytest.mark.parametrize("backend", ["fused", "blocked"])
    def test_split_equals_monolithic(self, kind, K, backend):
        rng = np.random.default_rng(0)
        plan = _random_plan(K, kind, rng)
        w, a = _chip_arrays(plan, rng)
        ev, ad = _window_inputs(K, rng)
        core, inst, cfg = _split_core(K, backend)
        out = _run(core, InterChipRouter(plan), (K,), w, a, ev, ad,
                   telemetry=True)
        spikes = np.asarray(out["spikes"])
        assert spikes.sum() > 0, "a silent run proves nothing"
        assert _counters(out)["routed_events"] > 0, \
            "routes must carry live traffic"

        mcore, _ = _mono_core(inst, cfg, K, backend)
        mrouter = InterChipRouter(monolithic_plan(plan))
        mout = _run(mcore, mrouter, (1,),
                    monolithic_weights(w)[None],
                    monolithic_weights(a)[None],
                    ev.reshape(W, T, 1, K * R), ad.reshape(W, T, 1, K * R))
        np.testing.assert_array_equal(
            spikes, np.asarray(mout["spikes"]).reshape(W, T, K, C))


class TestLinkBudget:
    """The never-silent overflow contract, per link: auto falls back
    bit-exactly AND counts; forced compact over budget visibly diverges
    AND counts."""

    def _runs(self, **router_kw):
        rng = np.random.default_rng(0)
        plan = _random_plan(4, "all2all", rng)
        w, a = _chip_arrays(plan, rng)
        ev, ad = _window_inputs(4, rng)
        core, _, _ = _split_core(4, "fused")
        return _run(core, InterChipRouter(plan, **router_kw), (4,),
                    w, a, ev, ad, telemetry=True)

    def test_modes_agree_within_budget(self):
        dense = self._runs(link_mode="dense")
        for mode in ("auto", "compact"):
            out = self._runs(link_mode=mode)
            np.testing.assert_array_equal(np.asarray(dense["spikes"]),
                                          np.asarray(out["spikes"]))
            assert _counters(out)["link_overflows"] == 0
        assert _counters(dense)["routed_events"] > 0

    def test_auto_fallback_is_bitexact_and_counted(self):
        dense = self._runs(link_mode="dense")
        tiny = self._runs(link_mode="auto", link_budget=4)
        np.testing.assert_array_equal(np.asarray(dense["spikes"]),
                                      np.asarray(tiny["spikes"]))
        c = _counters(tiny)
        assert c["link_overflows"] > 0
        assert c["link_events_max"] > 4

    def test_forced_compact_overflow_diverges_and_counts(self):
        dense = self._runs(link_mode="dense")
        tiny = self._runs(link_mode="compact", link_budget=4)
        assert not np.array_equal(np.asarray(dense["spikes"]),
                                  np.asarray(tiny["spikes"])), \
            "dropped link records must be visible downstream"
        assert _counters(tiny)["link_overflows"] > 0

    def test_step_budget_gates_auto(self):
        """The per-step bandwidth axis of the census: a tight
        ``link_step_budget`` trips the same counted fallback."""
        dense = self._runs(link_mode="dense")
        stepped = self._runs(link_mode="auto", link_step_budget=1)
        np.testing.assert_array_equal(np.asarray(dense["spikes"]),
                                      np.asarray(stepped["spikes"]))
        assert _counters(stepped)["link_overflows"] > 0


class TestClosedLoop:
    """run_training parity on the partitioned §5 network (the wafer mode
    of ``repro.core.hybrid``): mismatch draws, background events and
    exploration noise are drawn monolithically and resharded, so the
    learning trajectory is bit-identical for every chip count."""

    N = 8

    def _train(self, **kw):
        ecfg = hybrid.RSTDPConfig(trial_steps=128)
        out, _, meta = hybrid.run_training(n_trials=self.N, ecfg=ecfg,
                                           seed=0, **kw)
        return out, meta

    @staticmethod
    def _glob_w(w):
        K, I, c = w.shape
        return np.asarray(w).transpose(1, 0, 2).reshape(I, K * c)

    def test_k1_no_relay_matches_plain(self):
        plain, _ = self._train()
        wafer, meta = self._train(wafer=1, wafer_relay=False)
        assert meta["router"] is not None
        np.testing.assert_array_equal(plain["w_signed_final"],
                                      wafer["w_signed_final"][0])
        np.testing.assert_array_equal(plain["reward"].reshape(self.N, -1),
                                      wafer["reward"].reshape(self.N, -1))

    def test_chip_count_parity_with_relay(self):
        outs = {K: self._train(wafer=K, telemetry=True)[0]
                for K in (1, 2, 4)}
        base = self._glob_w(outs[1]["w_signed_final"])
        r1 = int(outs[1]["telemetry"]["routed_events"])
        assert r1 > 0, "the relay broadcast must carry traffic"
        for K in (2, 4):
            np.testing.assert_array_equal(
                base, self._glob_w(outs[K]["w_signed_final"]))
            np.testing.assert_array_equal(
                outs[1]["reward"].reshape(self.N, -1),
                outs[K]["reward"].reshape(self.N, -1))
            # every chip receives its own per-link broadcast copy
            assert int(outs[K]["telemetry"]["routed_events"]) == K * r1
            assert int(outs[K]["telemetry"]["link_overflows"]) == 0


def test_sharded_transport_matches_local_subprocess():
    """ppermute (ring) and masked all_gather (all2all) transports are
    bit-identical to the local one, for every link mode, on 8 fake CPU
    devices (subprocess: device count is fixed at jax init)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import dataclasses
import numpy as np, jax, jax.numpy as jnp
from repro.wafer import WaferTopology, make_plan, InterChipRouter, run_windows
from repro.core.anncore import AnnCore
from repro.verif.mismatch import sample_instance
from repro.configs.bss2 import BSS2
from repro.launch.mesh import make_smoke_mesh
from repro.parallel.sharding import ShardingCtx
from repro.obs import trace as obs

mesh = make_smoke_mesh((4, 2))
ctx = ShardingCtx(mesh=mesh)
K, R, C, T, W = 4, 16, 8, 32, 3
cfg = dataclasses.replace(BSS2.reduced(), n_rows=R, n_cols=C)
rng = np.random.default_rng(0)
inst = sample_instance(cfg, jax.random.PRNGKey(3), (K,))
core = AnnCore(cfg, inst, backend="fused")
w = rng.integers(20, 60, (K, R, C)).astype(np.int8)
ev = (rng.random((W, T, K, R)) < 0.3).astype(np.float32)
ad = np.zeros((W, T, K, R), np.int8)

for kind in ("ring", "all2all"):
    routes = []
    for s in range(K):
        dsts = [(s + 1) % K] if kind == "ring" else list(range(K))
        for d in dsts:
            for _ in range(4):
                routes.append((s, int(rng.integers(C)), d,
                               int(rng.integers(R)), 7))
    plan = make_plan(WaferTopology(K, kind), R, C, routes)
    a = np.zeros((K, R, C), np.int8)
    relay = plan.relay_rows()
    for k in range(K):
        a[k][relay[k]] = 7

    def run_with(router):
        st = core.init_state((K,))
        st = st._replace(syn=st.syn._replace(weights=jnp.asarray(w),
                                             addresses=jnp.asarray(a)))
        _, out = jax.jit(lambda s, e, d: run_windows(
            core, router, s, e, d, telemetry=obs.init_telemetry()))(
                st, jnp.asarray(ev), jnp.asarray(ad))
        return (np.asarray(out["spikes"]),
                int(np.asarray(out["telemetry"].routed_events)))

    for mode in ("dense", "compact", "auto"):
        s_loc, n_loc = run_with(InterChipRouter(plan, link_mode=mode))
        r_sh = InterChipRouter(plan, ctx=ctx, link_mode=mode)
        assert r_sh._axis == "data", r_sh._axis
        s_sh, n_sh = run_with(r_sh)
        np.testing.assert_array_equal(s_loc, s_sh)
        assert n_loc == n_sh, (kind, mode, n_loc, n_sh)
        assert s_loc.sum() > 0 and n_loc > 0
print("WAFER_SHARDED_OK")
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert "WAFER_SHARDED_OK" in r.stdout, \
        r.stdout[-2000:] + r.stderr[-3000:]


def test_per_device_wafer_matches_local_subprocess():
    """A sharded wafer with Pallas kernels (interpret mode here, native on
    TPU) places one chip's state per device and runs each device's chips
    under ``shard_map``; the closed loop (spikes, rewards, weights) is
    bit-identical to the local transport on one device. 4 fake CPU
    devices (subprocess: device count is fixed at jax init)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, "src")
import dataclasses
import numpy as np, jax, jax.numpy as jnp
from repro.configs.bss2 import BSS2
from repro.core.hybrid import (RSTDPConfig, make_experiment,
                               make_scanned_training)
from repro.launch.mesh import make_smoke_mesh
from repro.parallel.sharding import ShardingCtx

K = 4
ecfg = RSTDPConfig(n_inputs=16, n_neurons=32, pattern_size=5,
                   trial_steps=32)
cfg = dataclasses.replace(BSS2.reduced(), n_rows=32, n_cols=32)
ctx = ShardingCtx(mesh=make_smoke_mesh((K, 1)))
stims = jnp.asarray([1, 2, 0], jnp.int32)
out = {}
for name, wctx in (("local", None), ("sharded", ctx)):
    init, _, meta = make_experiment(
        cfg=cfg, ecfg=ecfg, instance_key=jax.random.PRNGKey(0), wafer=K,
        wafer_ctx=wctx, backend="blocked", kernel_impl="interpret",
        kernel_block=16)
    st = init(jax.random.PRNGKey(1))
    if wctx is not None:
        assert meta["router"]._axis == "data"
        shards = st.core.syn.weights.addressable_shards
        assert sorted(s.device.id for s in shards) == [0, 1, 2, 3]
        assert all(s.data.shape[0] == 1 for s in shards)
    st, hist = make_scanned_training(meta["scanned_training"])(st, stims)
    out[name] = jax.device_get((st.core.syn.weights, st.w_signed,
                                hist["rates"], hist["reward"]))
for a, b in zip(out["local"], out["sharded"]):
    np.testing.assert_array_equal(a, b)
assert out["local"][2].sum() > 0
print("PER_DEVICE_OK")
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert "PER_DEVICE_OK" in r.stdout, \
        r.stdout[-2000:] + r.stderr[-3000:]
