"""CPU tests of the chip benchmark's yardstick.

  * the trace reduction: busy time is the union of the device op
    intervals, and the layers' times plus glue add up to it, on a
    synthetic timeline and on a recorded excerpt of a chip trace;
  * the ``synray`` operation and byte count against the kernel's shapes,
    and the peaks table (an unknown device kind raises);
  * the comparison that decides ``correct``: a sound run of a cell at a
    tiny size passes, and the same run with its timed path broken
    underneath fails, once for each fault the cells can have.

Nothing here describes a TPU topology or needs a chip: every run is on
the CPU, at a size a test run holds, through ``driver.run_cell`` (the
harness's look for a chip is in ``run.py``, which these tests skip).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
EXCERPT = os.path.join(BENCH, "data", "trace_excerpt.json")


def _synthetic():
    # one device: a module op containing two kernels, an overlapping op,
    # and a gap; times in ns
    ops = [["m", "while.1", 0.0, 100.0],
           ["m", "k.syn", 10.0, 20.0],
           ["m", "k.corr", 40.0, 30.0],
           ["m", "f.ppu", 60.0, 20.0],       # overlaps k.corr
           ["m", "f.glue", 150.0, 50.0]]
    sites = {"m": {
        "while.1": ["while", [["/x/repro/core/hybrid.py", "scanned", 1]]],
        "k.syn": ["custom-call",
                  [["/x/repro/kernels/synray/kernel.py", "f", 2]]],
        "k.corr": ["custom-call",
                   [["/x/repro/kernels/corr/kernel.py", "f", 3]]],
        "f.ppu": ["fusion", [["/x/repro/core/hybrid.py",
                              "make_experiment.<locals>._signed_rule", 4],
                             ["/x/repro/core/hybrid.py", "scanned", 1]]],
        "f.glue": ["fusion", []]}}
    return dict(devices={"/device:TPU:0": ops},
                host=[["bench/window", 0.0, 250.0],
                      ["bench/call", 0.0, 120.0],
                      ["bench/readback", 210.0, 30.0]], sites=sites)


def test_reduce_union_and_attribution():
    from bench.harness import trace
    table = trace.load_table(os.path.join(BENCH, "layers.json"))
    r = trace.reduce(_synthetic(), table)
    # busy = [0, 100] u [150, 200] = 150 ns of a 250 ns window
    assert r["busy_s"] == pytest.approx(150e-9)
    assert r["window_s"] == pytest.approx(250e-9)
    assert sum(r["layers_s"].values()) == pytest.approx(r["busy_s"])
    lay = r["layers_s"]
    assert lay["synaptic phase"] == pytest.approx(20e-9)
    # k.corr runs 40..70, f.ppu (started later) owns 60..80
    assert lay["correlation sensors"] == pytest.approx(20e-9)
    assert lay["PPU rule"] == pytest.approx(20e-9)
    assert lay["experiment driver glue"] == pytest.approx(90e-9)
    assert r["kernels"]["repro/kernels/synray/kernel.py"] == dict(
        seconds=pytest.approx(20e-9), launches=1)
    # the gaps 100..150 (host in no benchmark span: call ended at 120 ..)
    # and 200..250 (readback)
    labels = dict((round(s * 1e9), n) for n, s in r["idle_gaps"])
    assert labels == {50: "bench/readback"} or len(r["idle_gaps"]) == 2


HLO = """HloModule jit_step, is_scheduled=true

FileNames
1 "/src/repro/kernels/corr/kernel.py"
2 "/src/repro/core/hybrid.py"

FunctionNames
1 "correlation_window_pallas"
2 "make_experiment.<locals>._signed_rule"

FileLocations
1 {file_name_id=1 function_name_id=1 line=87 end_line=87}
2 {file_name_id=2 function_name_id=2 line=503 end_line=503}

StackFrames
1 {file_location_id=1 parent_frame_id=1}
2 {file_location_id=2 parent_frame_id=2}

%fused_computation (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %mul.1 = f32[4]{0} multiply(%p, %p), metadata={op_name="x" stack_frame_id=2}
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %corr.3 = f32[4]{0:T(8,128)} custom-call(%a), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(step)/pallas_call" stack_frame_id=1}, backend_config={"a":{"b":1}}
  ROOT %fusion.7 = f32[4]{0} fusion(%corr.3), kind=kLoop, calls=%fused_computation
}
"""


def test_hlo_sites_reads_stacks_of_kernels_and_fusions():
    """A TPU program's text: a Pallas call's own metadata, not its
    ``kernel_metadata={}``, names its stack; a fusion without metadata
    takes its fused instructions' stack."""
    from bench.harness import trace
    module, sites = trace.hlo_sites(HLO)
    assert module == "jit_step"
    assert sites["corr.3"] == ["custom-call", [
        ["/src/repro/kernels/corr/kernel.py", "correlation_window_pallas",
         87]]]
    assert sites["fusion.7"][1][0][1].endswith("_signed_rule")
    table = trace.load_table(os.path.join(BENCH, "layers.json"))
    assert trace.layer_of(sites["corr.3"], table) == "correlation sensors"
    assert trace.layer_of(sites["fusion.7"], table) == "PPU rule"
    assert trace.layer_of(sites["a"], table) == "experiment driver glue"


@pytest.mark.skipif(not os.path.exists(EXCERPT),
                    reason="no recorded excerpt of a chip trace")
def test_reduce_recorded_excerpt():
    """A recorded excerpt of a chip trace (one call of a cell): the
    reduction's busy time is the union of the op intervals, computed here
    again independently, and layers plus glue add up to it."""
    from bench.harness import trace
    with open(EXCERPT) as f:
        events = json.load(f)
    table = trace.load_table(os.path.join(BENCH, "layers.json"))
    r = trace.reduce(events, table)
    lo, hi = trace.host_window(events)
    unions = []
    for plane, ops in events["devices"].items():
        iv = sorted((max(s, lo), min(s + d, hi)) for _, _, s, d in ops
                    if s + d > lo and s < hi)
        union, end = 0.0, -np.inf
        for a, b in iv:
            if b > end:
                union += b - max(a, end)
                end = b
        if ops:
            unions.append(union)
    assert unions and min(unions) > 0
    assert r["busy_s"] == pytest.approx(np.mean(unions) * 1e-9, rel=1e-9)
    assert sum(r["layers_s"].values()) == pytest.approx(r["busy_s"],
                                                        rel=1e-9)
    assert r["busy_s"] <= r["window_s"]
    named = [k for k, v in r["layers_s"].items()
             if v > 0 and k != table["unattributed"]]
    assert len(named) >= 3, r["layers_s"]


def test_synray_cost_matches_kernel_shapes():
    import jax
    import jax.numpy as jnp
    from bench.harness.roofline import synray_cost
    N, B, R, C = 3, 128, 128, 512
    ops, nbytes = synray_cost(N, B, R, C)
    # operands as the kernel is handed them (event addresses widened to
    # int32 by its wrapper) and its result, once each
    shapes = [((N, B, R), jnp.float32), ((N, B, R), jnp.int32),
              ((N, R, C), jnp.int8), ((N, R, C), jnp.int8),
              ((N, B, C), jnp.float32)]
    assert nbytes == sum(int(np.prod(s)) * jnp.dtype(d).itemsize
                         for s, d in shapes)
    # useful operations: the contraction of the kernel's reference
    S = jax.ShapeDtypeStruct
    cost = jax.jit(lambda e, w: jnp.einsum("br,rc->bc", e, w)).lower(
        S((B, R), jnp.float32), S((R, C), jnp.float32)
    ).compile().cost_analysis()
    assert ops == N * cost["flops"] == N * 2 * B * R * C


def test_peaks_table_and_unknown_device():
    from bench.harness import roofline
    p = roofline.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")
    share, bound = roofline.roofline_share(2e12, 1e9, 1.0, "TPU v5 lite")
    assert bound == "compute" and share == pytest.approx(100 * 2e12 / 197e12)


# ---------------------------------------------------------------------------
# the comparison that decides ``correct``, sound and broken
# ---------------------------------------------------------------------------

def tiny(workload: str) -> dict:
    """The cell's files, cut to a size a CPU test holds: a 32 x 16 chip,
    T = 32, a fleet of 64 with 32 checked instances; the checked
    experiments, where every call is one, and the limits are the cell's
    own."""
    sys.path[0:0] = [p for p in (ROOT, os.path.join(ROOT, "src"))
                     if p not in sys.path]
    from bench import run as brun
    if workload == "s5_wafer4":
        spec = wafer_spec()
    else:
        spec = brun.load_spec(workload)
    cfg = spec["config"]
    k = int(cfg["deployment"].get("wafer", 1))
    cfg["chip"].update(n_rows=32, n_cols=16 * k, n_neurons=16 * k)
    cfg["experiment"].update(n_inputs=16, n_neurons=16 * k, pattern_size=5,
                             trial_steps=32)
    if "fleet" in cfg["deployment"]:
        cfg["deployment"]["fleet"] = 64
    spec["traffic"]["trials_per_call"] = 3
    spec["limits"].update(check_instances=32)
    return spec


# the wafer runs the TPU path's kernels in interpret mode: the CPU's jnp
# form of the dense synaptic window resolves event addresses once per
# window (``const_addr``), which the relay rows' per-step address 63
# breaks, while the TPU kernels match addresses at every step
WAFER_KW = dict(backend="blocked", kernel_impl="interpret", kernel_block=16)


def wafer_spec() -> dict:
    """The four-chip cell's files (not yet a cell of BENCHMARK.json:
    its chip runs are still to come), assembled as ``run.load_spec``
    assembles a cell's."""
    from bench import run as brun
    return dict(spec=dict(end_to_end=[], per_layer=[]),
                workload=dict(name="s5_wafer4", chips=4),
                config=brun.load_json(BENCH, "configs",
                                      "bss2_s5_wafer4.json"),
                traffic=brun.load_json(BENCH, "traffic",
                                       "s5_experiments.json"),
                limits=brun.load_json(BENCH, "limits", "s5_wafer4.json"))


def run_tiny(workload: str, wrap=None, seed: int = 2 ** 33 + 5,
             traced: bool = False) -> dict:
    from bench import run as brun
    from bench.harness import driver
    spec = tiny(workload)
    # a short window: it runs on until every checked experiment has run
    seconds = 0.2
    res, _ = driver.run_cell(
        spec, seed, seconds, traced, brun.metrics_for(spec["spec"], workload,
                                                  traced),
        brun.reader, lambda m: None, time.perf_counter(),
        build_kw=WAFER_KW if "wafer" in workload else None, wrap=wrap,
        device_planes="/host:CPU")
    return res


def frozen_state(cell):
    """A step that returns its state unchanged: every trial of a call
    starts from the call's input state (only the key advances)."""
    import jax
    trial = cell.trial

    def scanned(state, stims):
        def body(st, stim):
            new, m = trial(st, stim)
            return st._replace(key=new.key), m
        return jax.lax.scan(body, state, stims)
    cell.scanned = jax.jit(scanned)


def half_fleet(cell):
    """Half of the fleet left out: the second half's outputs are copies
    of the first half's."""
    import jax.numpy as jnp
    run = cell.scanned

    def scanned(state, stims):
        state, hist = run(state, stims)
        h = {k: jnp.concatenate([v[:, :v.shape[1] // 2]] * 2, axis=1)
             if v.ndim > 1 else v for k, v in hist.items()}
        return state, h
    cell.scanned = scanned


def altered_answer(cell):
    """Answers altered where they are produced: the second trial of every
    call reports the first trial's spike counts."""
    run = cell.scanned

    def scanned(state, stims):
        state, hist = run(state, stims)
        rates = hist["rates"]
        return state, dict(hist, rates=rates.at[1].set(rates[0]))
    cell.scanned = scanned


def test_sound_tiny_runs_are_correct():
    for wl in ("s5_fleet", "s5_chip"):
        res = run_tiny(wl)
        assert res["correct"], (wl, res["check"])
        assert set(res["check"]) == {"networks_off"}


def test_traced_path_attributes_the_program_ops():
    """The traced path end to end on the CPU, whose XLA ops stand in for
    the device's: the compiled programs' stack metadata is read, and
    every layer of the single-chip loop is found in the trace (numbers
    from this run describe the CPU and are never reported)."""
    res = run_tiny("s5_chip", traced=True)
    assert res["correct"]
    names = set(res["metrics"])
    assert {"synapse_us.loop", "neuron_us.loop", "corr_us.loop",
            "ppu_us.loop", "glue_us.loop", "idle_share.loop"} <= names
    assert 0.0 <= res["metrics"]["idle_share.loop"]["value"] < 1.0
    assert res["breakdown"]["device_ops"]


@pytest.mark.parametrize("fault,workload", [
    ("frozen_state", "s5_chip"), ("frozen_state", "s5_fleet"),
    ("half_fleet", "s5_fleet"), ("altered_answer", "s5_fleet"),
    ("altered_answer", "s5_chip")])
def test_broken_timed_path_is_not_correct(fault, workload):
    res = run_tiny(workload, wrap=globals()[fault])
    assert not res["correct"], (fault, workload, res["check"])


WAFER_SCRIPT = r"""
import sys, json
sys.path[0:0] = [{root!r}, {src!r}]
from bench import test_bench as tb
import jax.numpy as jnp
out = {{}}
out["sound"] = tb.run_tiny("s5_wafer4")["correct"]

def no_exchange(cell):
    router = cell.meta["router"]
    def route(spikes, telemetry=None, routed_in=None):
        T = spikes.shape[0]
        return jnp.zeros((T, router.K, router.R), jnp.float32), telemetry
    router.route = route
out["no_exchange"] = tb.run_tiny("s5_wafer4", wrap=no_exchange)["correct"]
print(json.dumps(out))
"""


def test_wafer_sound_and_without_exchange():
    """Four forced CPU devices in a child process (the device count is
    fixed when JAX starts): the sharded wafer cell passes, and fails with
    the exchange between chips left out."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = WAFER_SCRIPT.format(root=ROOT, src=os.path.join(ROOT, "src"))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out == {"sound": True, "no_exchange": False}
