"""The plain reference of the Section 5 closed loop.

A straightforward, per-step implementation of the experiment the
configuration file states: Tsodyks-Markram STP in the drivers, the 6-bit
synapse array with address matching, the AdEx neuron (exponential Euler,
refractory clamp), the correlation sensors, the 8-bit CADC, the signed
R-STDP rule with the running mean reward (Eq. 2), the Dale row rewrite
into 6-bit weights, and, in wafer mode, the relay broadcast of every
neuron's spikes to every chip one trial later. It follows arXiv:2003.11455
Sections 2 and 5 and the equations the configuration names; it imports
nothing of the program and takes nothing the program made. The mismatch
realisation, the stimulus and the exploration noise are drawn here from
the same keys with ``jax.random``, as the configuration's seeds define
them.

It runs one time step at a time, on the device, in blocks of sampled
instances, after the measured window has closed.
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness.keys import run_key

# per-neuron analog parameters, in the order the mismatch draw uses
NEURON_PARAMS = ("g_leak", "e_leak", "v_thres", "e_reset", "v_exp",
                 "delta_t", "tau_w", "a", "b", "tau_refrac", "tau_syn_exc",
                 "tau_syn_inh", "c_mem")
# circuit-specific spreads; every other parameter spreads by capmem
NEURON_SIGMA = {"g_leak": ("sigma_g_leak", False),
                "tau_syn_exc": ("sigma_tau_syn", False),
                "tau_syn_inh": ("sigma_tau_syn", False),
                "v_thres": ("sigma_v_thres", True)}
CALIB_STEP = 0.1          # STP efficacy per calibration LSB
CORR_SAT = 1023.0         # correlation capacitor saturation
W_SIGNED_MAX = 45.0       # PPU clip of the signed weight
# bfloat16 terms each float32 operand of the synaptic matmul is carried
# in: "highest" keeps all 24 bits, "high" (three passes) two bfloat16
# terms (16 bits), "default" one. The weights are 6-bit integers, exact in
# bfloat16, so the events' efficacies are the operand that loses bits.
PRECISION_TERMS = {"highest": None, "high": 2, "default": 1}


def bf16_round(x):
    """float32 rounded to the nearest bfloat16 (ties to even), on the bit
    pattern: a float round trip through bfloat16 may be folded away by
    the compiler as excess precision."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def bf16_terms(x, n: int):
    """``x`` as the sum of its ``n`` leading bfloat16 terms."""
    out, rest = jnp.zeros_like(x), x
    for _ in range(n):
        term = bf16_round(rest)
        out, rest = out + term, rest - term
    return out


def draw_instance(chip: Dict, key, prefix) -> Dict:
    """The mismatch realisation of ``prefix`` chips, drawn op by op."""
    mm, nrn = chip["mismatch"], chip["neuron"]
    r, c = chip["n_rows"], chip["n_cols"]
    keys = jax.random.split(key, len(NEURON_PARAMS) + 5)
    params = {}
    for i, name in enumerate(NEURON_PARAMS):
        v = jnp.broadcast_to(jnp.full((c,), nrn[name], jnp.float32),
                             (*prefix, c))
        n = jax.random.normal(keys[i], (*prefix, c))
        attr, additive = NEURON_SIGMA.get(name, ("sigma_capmem", False))
        v = v + mm[attr] * n if additive else v * (1.0 + mm[attr] * n)
        params[name] = v
    k_wg, k_so, k_co, k_cg, _ = keys[-5:]
    return dict(
        params=params,
        weight_gain=1.0 + mm["sigma_weight_gain"]
        * jax.random.normal(k_wg, (*prefix, c)),
        stp_offset=mm["sigma_stp_offset"]
        * jax.random.normal(k_so, (*prefix, r)),
        stp_calib=jnp.full((*prefix, r), 2 ** (chip["calib_bits"] - 1),
                           jnp.int32),
        cadc_offset=mm["sigma_cadc_offset"]
        * jax.random.normal(k_co, (*prefix, c)),
        cadc_gain=1.0 + mm["sigma_cadc_gain"]
        * jax.random.normal(k_cg, (*prefix, c)))


def pattern_masks(exp: Dict):
    k = exp["pattern_size"]
    shared = int(round(exp["overlap"] * k))
    a = np.zeros(exp["n_inputs"], np.float32)
    b = np.zeros(exp["n_inputs"], np.float32)
    a[:k] = 1
    b[:shared] = 1
    b[k:2 * k - shared] = 1
    return a, b


def burst_steps(exp: Dict) -> np.ndarray:
    """[T] 1.0 on the steps of the pattern bursts."""
    T = exp["trial_steps"]
    at = np.linspace(T // 8, T - T // 8, exp["pattern_repeats"],
                     dtype=np.float32).astype(np.int64)
    d = np.arange(T)[:, None] - at[None, :]
    return np.any((d >= 0) & (d < exp["burst_width"]), 1).astype(np.float32)


def trial_keys(key, n: int):
    """(event key, rule key) of each of the first ``n`` trials."""
    out = []
    for _ in range(n):
        key, k_ev, k_rule = jax.random.split(key, 3)
        out.append((k_ev, k_rule))
    return out


def events(exp: Dict, k_ev, stim: int, draw_prefix) -> jnp.ndarray:
    """[T, *draw_prefix, 2I] 0/1 driver events of one trial: Poisson
    background on every input, synchronous bursts on the shown pattern;
    input i drives row 2i (exc) and row 2i+1 (inh)."""
    T, n_in = exp["trial_steps"], exp["n_inputs"]
    kb, _ = jax.random.split(k_ev)
    bg = (jax.random.uniform(kb, (T, *draw_prefix, n_in))
          < exp["bg_prob"]).astype(jnp.float32)
    a, b = pattern_masks(exp)
    shown = {1: a, 2: b}.get(int(stim), np.zeros_like(a))
    pat = (burst_steps(exp)[:, None] * shown[None, :]).reshape(
        T, *([1] * len(draw_prefix)), n_in)
    ch = jnp.clip(bg + pat, 0.0, 1.0)
    return jnp.repeat(ch, 2, axis=-1)


def exploration(exp: Dict, k_rule, draw_shape) -> jnp.ndarray:
    """The rule's random walk xi, drawn at ``draw_shape``."""
    _, sub = jax.random.split(k_rule)
    return exp["noise"] * jax.random.normal(sub, draw_shape)


class Reference:
    """The closed loop of ``S`` independent networks of R rows x C
    columns. A fleet's sampled instances, one chip, or a wafer seen as one
    network of K * c columns (``relay``: every column's spikes come back
    one trial later on row ``column % R`` with ``relay_addr``)."""

    def __init__(self, chip: Dict, exp: Dict, inst: Dict,
                 precision: str = "highest", relay: bool = False,
                 relay_addr: int = 63):
        self.chip, self.exp, self.inst = chip, exp, inst
        self.terms = PRECISION_TERMS[precision]
        self.relay, self.relay_addr = relay, relay_addr
        self._trial = jax.jit(self._trial_fn)

    def init(self, S: int):
        ch, exp, p = self.chip, self.exp, self.inst["params"]
        R, C, n_in = ch["n_rows"], ch["n_cols"], exp["n_inputs"]
        z = lambda *s: jnp.zeros(s, jnp.float32)          # noqa: E731
        w_signed = exp["w_init"] * jnp.ones((S, n_in, C), jnp.float32)
        return dict(v=jnp.broadcast_to(p["e_leak"], (S, C)) + 0.0,
                    wad=z(S, C), iexc=z(S, C), iinh=z(S, C), refrac=z(S, C),
                    r=jnp.ones((S, R), jnp.float32), tpre=z(S, R),
                    tpost=z(S, C), ac=z(S, R, C), aa=z(S, R, C),
                    weights=self._rows(w_signed),
                    addresses=jnp.zeros((S, R, C), jnp.int8),
                    w_signed=w_signed, mean_r=z(S, C),
                    routed=z(exp["trial_steps"], S, R))

    def operand(self, x):
        return x if self.terms is None else bf16_terms(x, self.terms)

    @staticmethod
    def _rows(w_signed):
        """Dale rows: row 2i carries max(w, 0), row 2i+1 max(-w, 0), both
        rounded and saturated to 6 bits."""
        q = lambda w: jnp.clip(jnp.round(w), 0, 63).astype(jnp.int8)  # noqa
        exc = q(jnp.clip(w_signed, 0, None))
        inh = q(jnp.clip(-w_signed, 0, None))
        S, n_in, C = w_signed.shape
        return jnp.stack([exc, inh], axis=2).reshape(S, 2 * n_in, C)

    def _trial_fn(self, st, ev, xi, stim):
        """One trial of 128 steps and the PPU's update after it."""
        ch, exp, inst = self.chip, self.exp, self.inst
        p = inst["params"]
        dt, u = ch["dt"], ch["stp_u"]
        nrn = ch["neuron"]
        addr = jnp.zeros(ev.shape, jnp.int8)
        if self.relay:
            routed = st["routed"]
            ev = jnp.maximum(ev, routed)
            addr = jnp.where(routed > 0.0, jnp.int8(self.relay_addr), addr)
        scale = 1.0 + inst["stp_offset"] - (
            inst["stp_calib"].astype(jnp.float32)
            - 2 ** (ch["calib_bits"] - 1)) * CALIB_STEP
        recovery = 1.0 - jnp.exp(-dt / ch["stp_tau_rec"])
        de = jnp.exp(-dt / p["tau_syn_exc"])
        di = jnp.exp(-dt / p["tau_syn_inh"])
        alpha = jnp.exp(-dt / (p["c_mem"] / p["g_leak"]))
        aw = jnp.exp(-dt / p["tau_w"])
        lam_pre = np.float32(math.exp(-dt / nrn["tau_syn_exc"]))
        lam_post = np.float32(math.exp(-dt / nrn["tau_syn_exc"]))
        gain = inst["weight_gain"]
        w = st["weights"].astype(jnp.float32)
        stored = st["addresses"]

        def current(eff, a, rows):
            match = (stored[:, rows] == a[:, rows, None]).astype(jnp.float32)
            i = jnp.einsum("sr,src->sc", self.operand(eff[:, rows]),
                           w[:, rows] * match,
                           precision=jax.lax.Precision.HIGHEST)
            return i * gain * 60.0

        def step(s, x):
            pre, a = x
            eff = jnp.clip(u * s["r"] * scale, 0.0, 1.5) * pre
            r1 = s["r"] + (1.0 - s["r"]) * recovery
            r = jnp.clip(r1 - u * r1 * pre, 0.0, 1.0)
            i_exc = s["iexc"] * de + current(eff, a, slice(0, None, 2))
            i_inh = s["iinh"] * di + current(eff, a, slice(1, None, 2))
            v, wad, refrac = s["v"], s["wad"], s["refrac"]
            if nrn["adex"]:
                arg = jnp.clip((v - p["v_thres"]) / p["delta_t"], -20.0, 3.0)
                i_exp = p["g_leak"] * p["delta_t"] * jnp.exp(arg)
                spike_v = p["v_thres"] + 2.0 * p["delta_t"]
            else:
                i_exp, spike_v = 0.0, p["v_thres"]
            v_inf = p["e_leak"] + (i_exc - i_inh - wad + i_exp) / p["g_leak"]
            v_new = v_inf + (v - v_inf) * alpha
            w_inf = p["a"] * (v - p["e_leak"])
            w_new = w_inf + (wad - w_inf) * aw
            hold = refrac > 0.0
            v_new = jnp.where(hold, p["e_reset"], v_new)
            w_new = jnp.where(hold, wad, w_new)
            spk = (v_new > spike_v) & ~hold
            post = spk.astype(jnp.float32)
            tpre = s["tpre"] * lam_pre + pre
            tpost = s["tpost"] * lam_post + post
            new = dict(
                s, r=r, iexc=i_exc, iinh=i_inh,
                v=jnp.where(spk, p["e_reset"], v_new),
                wad=jnp.where(spk, w_new + p["b"], w_new),
                refrac=jnp.where(spk, p["tau_refrac"],
                                 jnp.maximum(refrac - dt, 0.0)),
                rc=s["rc"] + post, tpre=tpre, tpost=tpost,
                ac=jnp.minimum(s["ac"] + tpre[:, :, None] * post[:, None],
                               CORR_SAT),
                aa=jnp.minimum(s["aa"] + pre[:, :, None] * tpost[:, None],
                               CORR_SAT))
            return new, post

        s0 = dict(st, rc=jnp.zeros_like(st["v"]))
        s1, spikes = jax.lax.scan(step, s0, (ev, addr))
        rates = s1["rc"]
        # PPU: rate counters and CADC-digitized correlations
        C = rates.shape[-1]
        even = (jnp.arange(C) % 2 == 0).astype(jnp.float32)
        shown = jnp.where(stim == 1, even,
                          jnp.where(stim == 2, 1.0 - even, 0.0))
        fired = (rates >= exp["fire_thresh"]).astype(jnp.float32)
        reward = jnp.where(shown > 0, fired, 1.0 - fired)
        g = inst["cadc_gain"][:, None, :] * 8.0
        o = inst["cadc_offset"][:, None, :]
        lsb = 2 ** ch["cadc_bits"] - 1
        qc = jnp.clip(jnp.round(s1["ac"] * g + o), 0, lsb).astype(jnp.int32)
        qa = jnp.clip(jnp.round(s1["aa"] * g + o), 0, lsb).astype(jnp.int32)
        elig = (qc - qa)[:, 0::2].astype(jnp.float32) / float(lsb)
        mod = (reward - st["mean_r"])[:, None, :]
        dw = exp["eta"] * mod * elig + exp["eta_homeo"] * (
            (1.0 - reward) * (1.0 - 2.0 * fired))[:, None, :]
        w_signed = jnp.clip(st["w_signed"] + dw + xi, -W_SIGNED_MAX,
                            W_SIGNED_MAX)
        mean_r = st["mean_r"] + exp["gamma"] * (reward - st["mean_r"])
        R = ch["n_rows"]
        new = dict(s1, weights=self._rows(w_signed), w_signed=w_signed,
                   mean_r=mean_r, ac=jnp.zeros_like(s1["ac"]),
                   aa=jnp.zeros_like(s1["aa"]))
        new.pop("rc")
        if self.relay:
            T, S = spikes.shape[:2]
            new["routed"] = jnp.max(spikes.reshape(T, S, C // R, R), axis=2)
        out = dict(rates=rates, reward=reward, elig=elig, w=w_signed,
                   mean_reward=mean_r)
        return new, out

    def run(self, ev_xi_stims, S: int):
        """Trials from ``init``: ``ev_xi_stims`` yields, per trial, the
        [T, S, R] events, the [S, I, C] xi and the stimulus."""
        st = self.init(S)
        outs = []
        for ev, xi, stim in ev_xi_stims:
            st, out = self._trial(st, ev, xi, jnp.int32(stim))
            outs.append(jax.device_get(out))
        return {k: np.stack([o[k] for o in outs]) for k in outs[0]}


def reference_run(config: Dict, traffic: Dict, seed: int, call: int,
                  n_trials: int, sample: Optional[np.ndarray],
                  precision: str = "highest", block: int = 16) -> Dict:
    """The first ``n_trials`` trials of experiment ``call`` under
    ``--seed``: [n, S, ...] arrays of rates, reward, elig, w and
    mean_reward for the sampled fleet instances (or the one network),
    computed ``block`` instances at a time."""
    chip = config["chip"]
    exp = {**config["experiment"], **traffic.get("experiment", {})}
    dep = config.get("deployment", {})
    fleet = int(dep["fleet"]) if "fleet" in dep else None
    prefix = (fleet,) if fleet else ()
    inst_all = draw_instance(chip, jax.random.PRNGKey(
        int(config["instance_seed"])), prefix)
    stims = np.resize(traffic["stimuli"], n_trials)
    keys = trial_keys(run_key(seed, call), n_trials)
    n_in, C = exp["n_inputs"], chip["n_cols"]
    idx = np.asarray(sample) if fleet else np.zeros(1, np.int64)
    parts, census = [], []
    for lo in range(0, len(idx), block):
        sel = idx[lo:lo + block]
        if fleet:
            inst = jax.tree.map(lambda x: x[sel], inst_all)
        else:
            inst = jax.tree.map(lambda x: x[None], inst_all)
        ref = Reference(chip, exp, inst, precision,
                        relay=bool(dep.get("relay")) and "wafer" in dep)

        def feed():
            for (k_ev, k_rule), stim in zip(keys, stims):
                ev = events(exp, k_ev, stim, prefix)
                if lo == 0:
                    census.append(event_census(ev))
                xi = exploration(exp, k_rule, (*prefix, n_in, C))
                if fleet:
                    yield ev[:, sel], xi[sel], stim
                else:
                    yield ev[:, None], xi[None], stim
        parts.append(ref.run(feed(), len(sel)))
    out = {k: np.concatenate([p[k] for p in parts], axis=1)
           for k in parts[0]}
    out["census"] = census
    return out


def event_census(ev):
    """(most events of one instance and Dale half in the window, most in
    one step) of [T, ..., 2I] external events."""
    worst_n = worst_k = 0
    for half in (0, 1):
        per_step = jnp.sum(ev[..., half::2] != 0, axis=-1)
        worst_n = max(worst_n, int(jnp.max(jnp.sum(per_step, axis=0))))
        worst_k = max(worst_k, int(jnp.max(per_step)))
    return worst_n, worst_k
