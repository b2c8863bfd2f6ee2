"""The system under test: the Section 5 closed loop through the program's
normal path.

Every cell builds ``repro.core.hybrid.make_experiment(...)`` with
``backend="auto"`` and ``kernel_impl="auto"`` (the blocked backend with
native Pallas kernels on a TPU, the default density gate), jits its
``init`` and ``make_scanned_training(meta["scanned_training"])`` (one
program per call, state donated), and drives them as the traffic file
says:

  calls "carry"  one experiment whose state is carried from call to call
                 (a fleet running on);
  calls "fresh"  back-to-back experiments, each from a fresh seeded
                 ``init`` and one scanned call.

After each call the host reads back what a Section 5 user reads: the
per-trial ``reward`` and ``rates``. The ``elig``/``w`` history stays on
the device, except for the first call's first trials, which set-up keeps
for the comparison with the reference (``check.py``).

``--seed`` sets the run keys: the program draws the events and the
exploration noise from them inside the scan. The mismatch realisation is
the configuration's ``instance_seed``: the program closes over it as a
constant, so a per-seed instance would be a new program for every run.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, Optional

import numpy as np

from bench.harness.keys import run_key


def chip_config(config: Dict):
    from repro.configs.bss2 import BSS2Config, MismatchParams, NeuronParams
    chip = dict(config["chip"])
    neuron = NeuronParams(**chip.pop("neuron"))
    mismatch = MismatchParams(**chip.pop("mismatch"))
    return BSS2Config(**chip, neuron=neuron, mismatch=mismatch)


def experiment_params(config: Dict, traffic: Dict) -> Dict:
    """The configuration's experiment parameters with the traffic's
    overrides (stimulus statistics belong to the traffic)."""
    return {**config["experiment"], **traffic.get("experiment", {})}


@dataclasses.dataclass
class Cell:
    """One built cell: the jitted entry points and what drives them."""
    init: Any
    scanned: Any
    trial: Any                  # the program's one-trial entry
    stims: Any
    trials: int
    fresh: bool
    chips_per_trial: int        # emulated chips one trial advances
    prefix: tuple
    devices: list
    meta: Dict


def build(config: Dict, traffic: Dict, **exp_kw) -> Cell:
    """Build the program for a configuration and a traffic mix.
    ``exp_kw`` is forwarded to ``make_experiment`` (tests use it to run
    the same cell with Pallas kernels in interpret mode on the CPU)."""
    import jax
    import jax.numpy as jnp
    from repro.core.hybrid import (RSTDPConfig, make_experiment,
                                   make_scanned_training)

    cfg = chip_config(config)
    ecfg = RSTDPConfig(**experiment_params(config, traffic))
    dep = config.get("deployment", {})
    kw = dict(backend="auto", kernel_impl="auto")
    kw.update(exp_kw)
    prefix, chips, devices = (), 1, jax.devices()[:1]
    if "fleet" in dep:
        prefix = (int(dep["fleet"]),)
        chips = prefix[0]
    if "wafer" in dep:
        from repro.launch.mesh import make_smoke_mesh
        from repro.parallel.sharding import ShardingCtx
        K = int(dep["wafer"])
        devices = jax.devices()[:K]
        kw.update(wafer=K, wafer_topology=dep.get("topology", "all2all"),
                  wafer_relay=bool(dep.get("relay", True)),
                  wafer_ctx=ShardingCtx(mesh=make_smoke_mesh((K, 1))))
        chips = K
    init, trial, meta = make_experiment(
        cfg=cfg, ecfg=ecfg, prefix=prefix,
        instance_key=jax.random.PRNGKey(int(config["instance_seed"])),
        **kw)
    n = int(traffic["trials_per_call"])
    pattern = list(traffic["stimuli"])
    if n % len(pattern):
        raise ValueError("trials_per_call must hold whole stimulus cycles, "
                         "so that every call sends the same sequence")
    stims = jnp.asarray(np.resize(pattern, n), jnp.int32)
    return Cell(init=jax.jit(init),
                scanned=make_scanned_training(meta["scanned_training"]),
                trial=trial,
                stims=stims, trials=n,
                fresh=traffic["calls"] == "fresh",
                chips_per_trial=chips, prefix=prefix, devices=devices,
                meta=meta)


CHECKED = ("rates", "reward", "elig", "w", "mean_reward")


def check_slice(hist, n_trials: int, sample: Optional[np.ndarray]):
    """The first ``n_trials`` trials of the per-trial history, for the
    sampled instances of a fleet (``sample``) or the whole network, in
    the reference's layout [trial, instance, ...]: one instance of all
    columns for a single chip or a wafer (chips side by side)."""
    import jax
    out = {}
    for k in CHECKED:
        x = hist[k][:n_trials]
        if sample is not None:
            x = x[:, sample]
        out[k] = np.asarray(jax.device_get(x))
    return out


def to_network(x: np.ndarray, kind: str) -> np.ndarray:
    """[n, ...] program layout -> [n, S, ..., C] reference layout. A fleet
    is already instance-major; one chip gains an instance axis; a wafer's
    K chips of c columns become one network of K * c columns (chip k
    holds columns k * c ..)."""
    if kind == "fleet":
        return x
    if kind == "chip":
        return x[:, None]
    x = np.moveaxis(x, 1, -2)
    return x.reshape(*x.shape[:-2], -1)[:, None]


def kind_of(config: Dict) -> str:
    dep = config.get("deployment", {})
    return "fleet" if "fleet" in dep else "wafer" if "wafer" in dep \
        else "chip"


class CompileCounter:
    """Counts traces and backend compiles while it is armed."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.names = {dispatch.JAXPR_TRACE_EVENT,
                      dispatch.BACKEND_COMPILE_EVENT}
        self.count = 0
        self.armed = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if self.armed and name in self.names:
            self.count += 1


def window(cell: Cell, state, seed: int, seconds: float, first_call: int,
           log, annotate=None, keep=(), n_keep: int = 0):
    """Drive the cell for ``seconds``, and on until every call in
    ``keep`` has run. Returns (state, stats, kept): the first ``n_keep``
    trials' history of the calls in ``keep``, left on the device until
    the window has closed."""
    import jax
    span = annotate or (lambda name: _NullSpan())
    kept = {}
    calls = trials = 0
    call_s, readback_s = [], []
    t0 = time.perf_counter()
    t_end = t0
    while True:
        t_call = time.perf_counter()
        with span("call"):
            if cell.fresh:
                state = cell.init(run_key(seed, first_call + calls))
            state, hist = cell.scanned(state, cell.stims)
        t_rb = time.perf_counter()
        with span("readback"):
            reward, rates = jax.device_get((hist["reward"], hist["rates"]))
        if first_call + calls in keep:
            kept[first_call + calls] = {k: hist[k][:n_keep] for k in CHECKED}
        del hist
        t_end = time.perf_counter()
        if not (np.isfinite(reward).all() and np.isfinite(rates).all()):
            raise FloatingPointError("non-finite reward or rates")
        calls += 1
        trials += cell.trials
        call_s.append(t_end - t_call)
        readback_s.append(t_end - t_rb)
        if t_end - t0 >= seconds and first_call + calls > max(keep,
                                                               default=0):
            break
    stats = dict(calls=calls, trials=trials,
                 chip_trials=trials * cell.chips_per_trial,
                 window_s=t_end - t0, call_s=call_s, readback_s=readback_s,
                 t0=t0, t1=t_end)
    return state, stats, kept


class _NullSpan:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False
