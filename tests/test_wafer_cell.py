"""The four-chip §5 wafer through the program's normal path.

``make_experiment`` promises constant per-row event addresses
(``const_addr``) only where the wiring keeps that promise: the relay
wafer's relay rows carry address 0 from the inputs and 63 from the bus,
so there the promise is withdrawn, while the kernels' density gate,
which the promise does not size, keeps its sizing. The ``s5_wafer4``
deployment (``bench/configs/bss2_s5_wafer4.json``), cut
to 32 x 16 per chip, then agrees with the plain reference on the CPU
with ``backend``/``kernel_impl`` "auto" (the jnp forms of the kernels),
and fails without the exchange between chips.
"""
import json
import os
import subprocess
import sys

import jax
import pytest

from repro.core import events, hybrid, synapse

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _core(wafer=None, relay=True, plan=None, prefix=(), n_inputs=16,
          n_neurons=64, T=32):
    """The emulated core ``make_experiment`` builds (geometry derived from
    the experiment: 2 * n_inputs rows, n_neurons columns in all)."""
    ecfg = hybrid.RSTDPConfig(n_inputs=n_inputs, n_neurons=n_neurons,
                              pattern_size=5, trial_steps=T)
    _, _, meta = hybrid.make_experiment(
        ecfg=ecfg, prefix=prefix, wafer=wafer, wafer_relay=relay,
        wafer_plan=plan, instance_key=jax.random.PRNGKey(0))
    return meta["core"]


@pytest.mark.parametrize("K,relay,promise", [
    (4, True, False), (2, True, False), (1, True, False),
    (4, False, True), (1, False, True)])
def test_promise_withdrawn_exactly_with_relay_rows(K, relay, promise):
    """The promise follows the plan: relay rows withdraw it, a plan that
    delivers into no rows keeps it. Either way the gate's threshold is
    left to the synapse layer's default."""
    from repro.wafer import s5_column_plan
    plan = s5_column_plan(K, 16, 64, relay=relay)
    assert plan.relay_rows().any() == relay
    core = _core(wafer=K, relay=relay)
    assert core.const_addr is promise
    assert core.sparse_threshold is None


def test_promise_withdrawn_for_an_explicit_plan_with_relay_rows():
    from repro.wafer import make_plan, WaferTopology
    plan = make_plan(WaferTopology(2, "all2all"), 32, 32,
                     [(0, 3, 1, 5, 7)])
    assert not _core(wafer=2, plan=plan).const_addr
    empty = make_plan(WaferTopology(2, "all2all"), 32, 32, [])
    assert _core(wafer=2, plan=empty).const_addr


@pytest.mark.parametrize("impl", ["pallas", "interpret"])
def test_relay_wafer_gate_capacities_at_full_size(impl):
    """At the deployment's own size (T = 256, 128 rows per Dale half) the
    relay wafer's kernels gate with the capacities of the 0.02 threshold,
    656 events and 16 per step, as the single chip's do: withdrawing the
    promise leaves the TPU program as it was."""
    T, R = 256, 128
    wafer = _core(wafer=4, n_inputs=R, n_neurons=2048, T=T)
    chip = _core(n_inputs=R, n_neurons=512, T=T)
    assert not wafer.const_addr and chip.const_addr

    def capacities(core):
        thr = synapse.default_sparse_threshold(impl, core.const_addr)
        return (events.default_max_events(T, R, thr),
                events.default_k_cap(R, thr))
    assert capacities(wafer) == capacities(chip) == (656, 16)


@pytest.mark.parametrize("prefix", [(), (3,)])
def test_single_chip_keeps_promise(prefix):
    """The single-chip experiment and fleets of it keep the promise and
    the gate's own default sizing."""
    core = _core(prefix=prefix)
    assert core.const_addr is True
    assert core.sparse_threshold is None


CHILD = r"""
import json, os, sys, time
sys.path[0:0] = [{root!r}, {src!r}]
import jax.numpy as jnp
from bench import run as brun
from bench import test_bench as tb
from bench.harness import driver


def run(wrap=None):
    spec = tb.tiny("s5_wafer4")
    res, _ = driver.run_cell(spec, 2 ** 33 + 5, 0.2, False, [], brun.reader,
                             lambda m: None, time.perf_counter(), wrap=wrap,
                             device_planes="/host:CPU")
    return dict(correct=res["correct"],
                networks_off=res["check"]["networks_off"]["value"])


def no_exchange(cell):
    router = cell.meta["router"]

    def route(spikes, telemetry=None, routed_in=None):
        T = spikes.shape[0]
        return jnp.zeros((T, router.K, router.R), jnp.float32), telemetry
    router.route = route


out = dict(sound=run(), no_exchange=run(no_exchange))
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def wafer_runs():
    """The tiny cell run twice in a child with 4 forced CPU devices (the
    device count is fixed when JAX starts): sound, and with the exchange
    between chips removed. No ``build_kw``: the normal path."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = CHILD.format(root=ROOT, src=os.path.join(ROOT, "src"))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_relay_wafer_normal_path_agrees_with_reference(wafer_runs):
    assert wafer_runs["sound"]["correct"], wafer_runs
    assert wafer_runs["sound"]["networks_off"] == 0, wafer_runs


def test_relay_wafer_without_exchange_is_not_correct(wafer_runs):
    assert not wafer_runs["no_exchange"]["correct"], wafer_runs
