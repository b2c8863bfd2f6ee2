"""CPU tests of the reading of the program's layer scopes and counters
(``bench/harness/scopes.py``).

  * ``bench/scopes.json`` declares the scopes the program declares;
  * a scope's time is its self time: a parent leaves out its children,
    and the scopes with the unscoped rest add up to the busy time;
  * a compiled program's ``op_name`` metadata gives each instruction its
    innermost declared scope, and a fusion without one takes its fused
    instructions' most common scope;
  * a traced window of a tiny cell on the CPU, with the sparse route
    forced: the packing scope holds time, the counting call reads the
    routes and repeats set-up's history bit for bit, and the host events
    hold JAX's dispatch of the entry point.

Numbers from these runs describe the CPU and are never reported.
"""
from __future__ import annotations

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[0:0] = [p for p in (ROOT, os.path.join(ROOT, "src"))
                 if p not in sys.path]


def test_scopes_table_matches_the_program():
    from bench.harness import scopes, trace
    from repro.obs.trace import LAYER_SCOPES
    table = trace.load_table(scopes.SCOPES)
    assert {k: v["parent"] for k, v in table["scopes"].items()} \
        == LAYER_SCOPES
    layers = {r["layer"] for r in trace.load_table(scopes.LAYERS)["layers"]}
    layers.add(table["unscoped"])
    assert {v["layer"] for v in table["scopes"].values()} <= layers
    for name, v in table["scopes"].items():
        if v["parent"]:
            assert v["layer"] == table["scopes"][v["parent"]]["layer"]


def _events():
    # one device, times in ns: a parent scope's op running round a child
    # scope's op, an op of another scope, an unscoped op, and a gap
    ops = [["m", "f.syn", 0.0, 100.0],
           ["m", "f.pack", 20.0, 30.0],
           ["m", "k.corr", 100.0, 40.0],
           ["m", "f.glue", 200.0, 20.0]]
    op_scopes = {"m": {"f.syn": "synaptic_phase", "f.pack": "pack_events",
                       "k.corr": "correlation_sensors", "f.glue": None}}
    host = [["bench/window", 0.0, 300.0]]
    return dict(devices={"/device:TPU:0": ops}, host=host), op_scopes


def test_reduce_scopes_self_time():
    from bench.harness import scopes, trace
    events, op_scopes = _events()
    r = scopes.reduce_scopes(events, op_scopes)
    s = {k: v * 1e9 for k, v in r["scopes_s"].items()}
    assert s == pytest.approx({"synaptic_phase": 70.0, "pack_events": 30.0,
                               "correlation_sensors": 40.0,
                               scopes.UNSCOPED: 20.0})
    assert sum(s.values()) == pytest.approx(r["busy_s"] * 1e9) \
        == pytest.approx(160.0)
    table = trace.load_table(scopes.SCOPES)
    layers = scopes.scope_layers(r["scopes_s"], table)
    assert layers["synaptic phase"] * 1e9 == pytest.approx(100.0)
    assert layers["experiment driver glue"] * 1e9 == pytest.approx(20.0)


HLO = """HloModule jit_step, entry_computation_layout={()->f32[4]}

%fused_computation (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %a = f32[4]{0} add(%p, %p), metadata={op_name="jit(step)/synaptic_phase/cond/branch_1_fun/pack_events/jit(_pack_regroup)/add"}
  ROOT %m = f32[4]{0} multiply(%a, %a), metadata={op_name="jit(step)/synaptic_phase/cond/branch_1_fun/pack_events/mul"}
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %stp.1 = f32[4]{0} negate(%x), metadata={op_name="jit(step)/synaptic_phase/stp/neg"}
  %synray.2 = f32[4]{0} custom-call(%stp.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/synaptic_phase/cond/branch_0_fun/dense_matmul/jit(synaptic_current_pallas)/synray"}
  %glue.3 = f32[4]{0} abs(%synray.2), metadata={op_name="jit(step)/while/body/abs"}
  ROOT %fusion.4 = f32[4]{0} fusion(%glue.3), kind=kLoop, calls=%fused_computation
}
"""


def test_hlo_scopes_reads_op_names_and_fusions():
    from bench.harness import scopes, trace
    declared = set(trace.load_table(scopes.SCOPES)["scopes"])
    module, s = scopes.hlo_scopes(HLO, declared)
    assert module == "jit_step"
    assert (s["stp.1"], s["synray.2"], s["glue.3"], s["fusion.4"]) == (
        "stp", "dense_matmul", None, "pack_events")
    # a program without scopes reads as unscoped everywhere
    _, bare = scopes.hlo_scopes(HLO.replace("synaptic_phase/", "")
                                .replace("pack_events/", "")
                                .replace("stp/", "")
                                .replace("dense_matmul/", ""), declared)
    assert set(bare.values()) == {None}


def test_gate_overflow_share():
    from bench.harness import scopes
    assert scopes.gate_overflow_share(
        dict(gated_windows=6, overflow_fallbacks=6)) == 1.0
    assert scopes.gate_overflow_share(
        dict(gated_windows=600, overflow_fallbacks=0)) == 0.0
    assert scopes.gate_overflow_share(
        dict(gated_windows=0, overflow_fallbacks=0)) == 0.0


def test_probe_of_a_tiny_cell_with_the_sparse_route():
    """The tiny single-chip cell on the CPU, sparse route forced: the
    scopes of the sparse route hold time, the dense route's none, the
    scopes add up to the busy time, and the counting call reads the
    route and repeats set-up's history."""
    from bench import test_bench as tb
    from bench.harness import scopes
    r = scopes.probe(tb.tiny("s5_chip"), 2 ** 33 + 5, 0.2, lambda m: None,
                     build_kw=dict(sparse_mode="always"),
                     device_planes="/host:CPU")
    s = r["scopes_s"]
    assert s["pack_events"] > 0 and s["gather_matmul"] > 0
    assert "dense_matmul" not in s
    for name in ("stp", "neuron_window", "correlation_sensors",
                 "ppu_rule", "event_generation"):
        assert s[name] > 0, name
    assert sum(s.values()) == pytest.approx(r["busy_s"], rel=1e-9)
    assert sum(r["scope_layers_s"].values()) == pytest.approx(
        sum(r["layers_s"].values()), rel=1e-9)
    c = r["counters"]
    assert c["sparse_windows"] == 2 * c["trials"] > 0
    assert c["dense_windows"] == c["gated_windows"] == 0
    assert r["gate_overflow_share"] == 0.0
    assert r["same_history"]
    assert "PjitFunction(scanned_training)" in r["host_names"]
    assert r["idle_gaps"] and all(len(g) == 3 for g in r["idle_gaps"])
