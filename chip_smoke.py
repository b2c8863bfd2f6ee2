"""Chip smoke test: the BrainScaleS-2 emulation's main path on a TPU.

  python chip_smoke.py              phases A and B on one chip
  python chip_smoke.py --chips 4    phase W only, on four chips

Phase A  one full-size window (256 rows x 512 columns, T=128) of the
         ``blocked`` backend with native Pallas kernels, against the
         per-step ``oracle`` with jnp kernels, both on the chip, from the
         same state and events, for each synaptic route: the dense
         ``synray`` kernel, the default density gate, and the sparse path
         (its jnp form on TPU) forced with room for every event.
Phase B  the §5 closed-loop R-STDP experiment as a fleet of 8 full-size
         chips (``make_experiment`` -> ``make_scanned_training``,
         ``backend="auto"``), 20 trials, run twice from a fresh ``init``.
Phase W  §5 in wafer mode over K=4 full-size chips (n_neurons=2048), ring
         and all2all: the shard_map transport over the four devices, with
         each device holding one emulated chip, against the local
         transport on one device.

Everything is made from a seed; nothing is downloaded. The script exits
non-zero, and prints no result line, when JAX finds no TPU or any check
fails. Its last line on success is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# Phase A tolerances (docs/exactness.md, "On the TPU"): the kernels may
# reduce in another order than the oracle, so currents need only agree to
# a few f32 ulps, and a current within rounding of the threshold may flip
# a spike. On a v5e the window came out exact (error 0.0, agreement 1.0),
# so the limits sit just above f32 reassociation, not at 1e-4 / 0.99.
CURRENT_RTOL = 1e-6        # max |I - I_ref| / max |I_ref| over the window
SPIKE_AGREEMENT = 0.999    # share of (t, neuron) spike entries that agree

FULL_ROWS, FULL_COLS, FULL_T = 256, 512, 128


def log(msg: str) -> None:
    print(msg, flush=True)


def device_info() -> dict:
    devs = jax.devices()
    return dict(platform=devs[0].platform, kind=devs[0].device_kind,
                count=len(devs))


# ---------------------------------------------------------------------------
# Phase A: one window, blocked + Pallas vs the per-step oracle
# ---------------------------------------------------------------------------

def window_inputs(cfg, T: int, seed: int):
    """A §5-like state and event window for one chip: 6-bit weights on
    every row (inhibitory rows weaker), address 0 throughout, Poisson
    background at the §5 rate plus pattern-A bursts on 24 channels."""
    from repro.core.anncore import AnnCore
    from repro.core.hybrid import RSTDPConfig
    from repro.verif.mismatch import sample_instance

    R, C = cfg.n_rows, cfg.n_cols
    ecfg = RSTDPConfig(n_inputs=R // 2, n_neurons=C,
                       pattern_size=min(24, R // 4), trial_steps=T)
    k_inst, k_exc, k_inh, k_bg = jax.random.split(
        jax.random.PRNGKey(seed), 4)
    inst = sample_instance(cfg, k_inst, ())
    w = jnp.where(jnp.arange(R)[:, None] % 2 == 0,
                  jax.random.randint(k_exc, (R, C), 0, 64),
                  jax.random.randint(k_inh, (R, C), 0, 16)).astype(jnp.int8)
    state = AnnCore(cfg, inst).init_state()
    state = state._replace(syn=state.syn._replace(weights=w))
    bursts = np.linspace(T // 8, T - T // 8, ecfg.pattern_repeats,
                         dtype=np.float32).astype(np.int64)
    to_burst = np.arange(T)[:, None] - bursts[None, :]
    is_burst = np.any((to_burst >= 0) & (to_burst < ecfg.burst_width), 1)
    pattern = np.zeros(R // 2, np.float32)
    pattern[:ecfg.pattern_size] = 1.0
    bg = jax.random.uniform(k_bg, (T, R // 2)) < ecfg.bg_prob
    ch = jnp.maximum(bg.astype(jnp.float32),
                     jnp.asarray(is_burst[:, None] * pattern[None, :]))
    ev = jnp.repeat(ch, 2, axis=-1)          # row 2i exc, 2i+1 inh
    return inst, state, ev, jnp.zeros(ev.shape, jnp.int8)


def _currents(core, state, ev, ad):
    _, i_exc, i_inh, _ = core._window_currents(state, ev, ad, 1)
    return jnp.stack([i_exc, i_inh])


def phase_a(rows=FULL_ROWS, cols=FULL_COLS, T=FULL_T, seed=0,
            kernel_impl="pallas") -> list:
    from repro.configs.bss2 import BSS2
    from repro.core.anncore import AnnCore

    cfg = dataclasses.replace(BSS2, n_rows=rows, n_cols=cols, n_neurons=cols)
    inst, state, ev, ad = window_inputs(cfg, T, seed)
    log(f"[A] window {rows}x{cols}, T={T}: "
        f"{int(jnp.sum(ev[:, 0::2]))} input events "
        f"(density {float(jnp.mean(ev)):.4f})")
    ref = AnnCore(cfg, inst, backend="oracle", kernel_impl="ref",
                  sparse_mode="never")
    # TPU f32 matmuls round their operands to bfloat16 unless asked not to
    with jax.default_matmul_precision("highest"):
        ref_cur = jax.jit(lambda s, e, a: _currents(ref, s, e, a))(
            state, ev, ad)
        _, ref_out = jax.jit(ref.run)(state, ev, ad)
    ref_spk = np.asarray(ref_out["spikes"])
    ref_cur = np.asarray(ref_cur)
    # the synaptic routes: the dense kernel, the density gate as the main
    # path runs it, and the sparse path forced with capacities that hold
    # the whole window (so it can drop nothing)
    routes = dict(
        dense=dict(sparse_mode="never"),
        gate=dict(sparse_mode="auto"),
        sparse=dict(sparse_mode="always", sparse_max_events=T * rows // 2,
                    sparse_k_cap=rows // 2))
    failures = []
    for route, route_kw in routes.items():
        core = AnnCore(cfg, inst, backend="blocked", kernel_impl=kernel_impl,
                       const_addr=True, **route_kw)
        run = jax.jit(core.run)
        text = run.lower(state, ev, ad).compile().as_text()
        n_custom = text.count("tpu_custom_call")
        _, out = run(state, ev, ad)
        cur = np.asarray(jax.jit(lambda s, e, a: _currents(core, s, e, a))(
            state, ev, ad))
        spk = np.asarray(out["spikes"])
        rel = float(np.max(np.abs(cur - ref_cur)) / np.max(np.abs(ref_cur)))
        agree = float(np.mean(spk == ref_spk))
        log(f"[A] {route} route: tpu_custom_call in compiled window: "
            f"{n_custom > 0} ({n_custom}); current max rel err {rel!r} "
            f"(limit {CURRENT_RTOL}); spikes {int(spk.sum())} vs oracle "
            f"{int(ref_spk.sum())}; spike agreement {agree!r} "
            f"(limit {SPIKE_AGREEMENT})")
        if kernel_impl == "pallas" and n_custom == 0:
            failures.append(f"A/{route}: no tpu_custom_call in the window")
        if not rel <= CURRENT_RTOL:
            failures.append(f"A/{route}: current rel err {rel}")
        if not agree >= SPIKE_AGREEMENT:
            failures.append(f"A/{route}: spike agreement {agree}")
        if ref_spk.sum() == 0 or spk.sum() == 0:
            failures.append(f"A/{route}: a side produced no spikes")
    return failures


# ---------------------------------------------------------------------------
# Phase B: the §5 closed loop as a fleet of full-size chips
# ---------------------------------------------------------------------------

def full_size_s5(n_neurons=FULL_COLS):
    """The full-size §5 configuration of ``hybrid.lower_bss2_cell``."""
    from repro.configs.bss2 import BSS2
    from repro.core.hybrid import RSTDPConfig

    cfg = dataclasses.replace(BSS2, n_cols=n_neurons, n_neurons=n_neurons)
    ecfg = RSTDPConfig(n_inputs=cfg.n_rows // 2, n_neurons=n_neurons,
                       pattern_size=24, trial_steps=FULL_T)
    return cfg, ecfg


def _train(init, meta, n_trials, seed):
    from repro.core.hybrid import make_scanned_training

    scanned = make_scanned_training(meta["scanned_training"])
    stims = jnp.asarray(np.resize([1, 2, 0], n_trials), jnp.int32)
    state = init(jax.random.PRNGKey(seed + 1))
    t0 = time.perf_counter()
    state, hist = scanned(state, stims)
    jax.block_until_ready((state, hist))
    return time.perf_counter() - t0, state, hist


def _identical(a, b) -> bool:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y), equal_nan=True)
        for x, y in zip(la, lb))


def phase_b(n_trials=20, fleet=8, seed=0, cfg_ecfg=None, **exp_kw) -> list:
    from repro.core.hybrid import make_experiment

    cfg, ecfg = cfg_ecfg or full_size_s5()
    init, _, meta = make_experiment(
        cfg=cfg, ecfg=ecfg, prefix=(fleet,),
        instance_key=jax.random.PRNGKey(seed), backend="auto", **exp_kw)
    log(f"[B] fleet of {fleet} chips, {cfg.n_rows}x{cfg.n_cols}, "
        f"T={ecfg.trial_steps}, {n_trials} trials, "
        f"backend={meta['core'].backend}")
    runs = []
    for label in ("cold", "warm"):
        secs, state, hist = _train(init, meta, n_trials, seed)
        runs.append(jax.device_get((state, hist)))
        log(f"[B] {label} run: {secs!r} s wall (ends in block_until_ready)")
    (st, hist), (st2, hist2) = runs
    w = np.asarray(st.core.syn.weights)
    reward = np.asarray(hist["reward"])
    spikes = float(np.sum(hist["rates"]))
    log(f"[B] mean reward first/last trial "
        f"{float(reward[0].mean())!r} / {float(reward[-1].mean())!r}; "
        f"spikes {spikes!r}; weights {int(w.min())}..{int(w.max())}")
    failures = []
    if not _identical(runs[0], runs[1]):
        failures.append("B: the two runs differ")
    if not np.isfinite(reward).all():
        failures.append("B: non-finite rewards")
    if w.min() < 0 or w.max() > 63:
        failures.append(f"B: weights outside 0..63 ({w.min()}..{w.max()})")
    if not spikes > 0:
        failures.append("B: no spikes")
    try:
        jax.tree.map(np.asarray, meta["inst"])
    except RuntimeError as e:       # a donated buffer reads as deleted
        failures.append(f"B: meta['inst'] unreadable after the runs: {e}")
    return failures


# ---------------------------------------------------------------------------
# Phase W: wafer mode over four devices vs the local transport
# ---------------------------------------------------------------------------

def ring_plan(K, n_rows, c_loc):
    """A ring plan with real traffic: every column announces its spikes on
    the next chip's row ``global column % n_rows`` with address 63, which
    matches no §5 synapse (the relay trick of ``s5_column_plan``)."""
    from repro.wafer import WaferTopology, make_plan

    routes = [(k, c, (k + 1) % K, (k * c_loc + c) % n_rows, 63)
              for k in range(K) for c in range(c_loc)]
    return make_plan(WaferTopology(K, "ring"), n_rows, c_loc, routes)


def _one_chip_per_device(state, K) -> bool:
    for leaf in jax.tree.leaves((state.core, state.w_signed)):
        shards = leaf.addressable_shards
        devs = {s.device for s in shards}
        if len(devs) != K or any(s.data.shape[0] != 1 for s in shards):
            return False
    return True


def phase_wafer(n_trials=10, K=4, seed=0, n_neurons=4 * FULL_COLS,
                **exp_kw) -> list:
    from repro.core.hybrid import make_experiment
    from repro.launch.mesh import make_smoke_mesh
    from repro.parallel.sharding import ShardingCtx

    cfg, ecfg = full_size_s5(n_neurons)
    c_loc = n_neurons // K
    ctx = ShardingCtx(mesh=make_smoke_mesh((K, 1)))
    failures = []
    for topology in ("ring", "all2all"):
        plan = ring_plan(K, cfg.n_rows, c_loc) if topology == "ring" else None
        res = {}
        for name, wctx in (("local", None), ("sharded", ctx)):
            init, _, meta = make_experiment(
                cfg=cfg, ecfg=ecfg, instance_key=jax.random.PRNGKey(seed),
                wafer=K, wafer_topology=topology, wafer_plan=plan,
                wafer_ctx=wctx, **exp_kw)
            placed = init(jax.random.PRNGKey(seed + 1))
            if wctx is not None and not _one_chip_per_device(placed, K):
                failures.append(f"W/{topology}: init state not one chip "
                                "per device")
            secs, state, hist = _train(init, meta, n_trials, seed)
            if wctx is not None and not _one_chip_per_device(state, K):
                failures.append(f"W/{topology}: final state not one chip "
                                "per device")
            spread = sorted(s.device.id for s in
                            state.core.syn.weights.addressable_shards)
            log(f"[W] {topology} {name}: {secs!r} s wall (cold), "
                f"transport {'shard_map' if meta['router']._axis else 'local'}"
                f", final weights on devices {spread}, spikes per trial "
                f"{float(np.sum(hist['rates'])) / n_trials!r}")
            res[name] = jax.device_get((state, hist))
        # the whole final state and history, membranes included: on a v5e
        # the per-device program reproduces the local one bit for bit
        same = _identical(res["local"], res["sharded"])
        w = np.asarray(res["sharded"][0].core.syn.weights)
        rates = [np.asarray(r[1]["rates"]) for r in res.values()]
        first = next((i for i in range(n_trials)
                      if not np.array_equal(rates[0][i], rates[1][i])), None)
        log(f"[W] {topology}: sharded == local bit for bit: {same} (first "
            f"trial whose spikes differ: {first}); mean reward "
            f"{float(np.mean(res['sharded'][1]['reward']))!r}; "
            f"weights {int(w.min())}..{int(w.max())}")
        if not same:
            failures.append(f"W/{topology}: sharded and local transports "
                            "differ")
        if not float(np.sum(res["sharded"][1]["rates"])) > 0:
            failures.append(f"W/{topology}: no spikes")
    return failures


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the wafer phase, over four chips")
    args = ap.parse_args()
    dev = device_info()
    log(f"device: platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}")
    if dev["platform"] != "tpu":
        log("FAIL: JAX found no TPU; this check does not run elsewhere")
        return 2
    if dev["count"] < args.chips:
        log(f"FAIL: --chips {args.chips} but JAX sees {dev['count']}")
        return 2

    from repro.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        failures = phase_wafer()
    else:
        failures = phase_a() + phase_b()
    log(f"total {time.perf_counter() - t0!r} s")
    for f in failures:
        log(f"FAIL: {f}")
    if failures:
        return 1
    print(json.dumps(dict(ok=True, device=dict(
        platform=dev["platform"], kind=dev["kind"], count=dev["count"]))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
