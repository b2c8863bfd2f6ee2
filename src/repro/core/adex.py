"""AdEx / LIF neuron dynamics (paper §2.1, Eqs. for V and w).

  C dV/dt = -g_L (V - E_L) + g_L Δ_T exp((V - V_T)/Δ_T) - w + I
  τ_w dw/dt = a (V - E_L) - w

Integration: exponential Euler on the leak/adaptation terms, explicit on
the exponential current (clipped — the silicon circuit saturates too).
Spike condition V > V_thres + spike latch -> reset + refractory hold, as in
the full-custom digital neuron backend.

All arrays broadcast over an arbitrary leading instance/batch shape:
states are [..., N] for N neurons.
"""
from __future__ import annotations

from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp


class NeuronState(NamedTuple):
    v: jnp.ndarray           # membrane potential [mV]
    w: jnp.ndarray           # adaptation current [pA]
    i_exc: jnp.ndarray       # excitatory synaptic current state [pA]
    i_inh: jnp.ndarray       # inhibitory synaptic current state [pA]
    refrac: jnp.ndarray      # remaining refractory time [us]


def init_state(shape, params) -> NeuronState:
    # distinct buffers per leaf: a shared zeros array would alias leaves
    # and break buffer donation of the whole state (donate-twice error).
    # ``v`` is a copy for the same reason: when the shapes already match,
    # broadcast_to/astype hand back the parameter's own buffer, and
    # donating the state would then delete the instance's e_leak
    def z():
        return jnp.zeros(shape, jnp.float32)
    v = jnp.array(jnp.broadcast_to(params["e_leak"], shape),
                  dtype=jnp.float32, copy=True)
    return NeuronState(v=v, w=z(), i_exc=z(), i_inh=z(), refrac=z())


SPIKE_CLAMP = 30.0   # mV above which the exponential term is clamped


def decay_factors(params: Dict, dt: float) -> Dict:
    """Time-invariant per-step decay terms (identical formulas to the ones
    ``step`` computes inline). Precompute once and pass as ``decays`` to
    hoist 4 exps + a division per step out of scan loops."""
    tau_m = params["c_mem"] / params["g_leak"]
    return dict(de=jnp.exp(-dt / params["tau_syn_exc"]),
                di=jnp.exp(-dt / params["tau_syn_inh"]),
                alpha=jnp.exp(-dt / tau_m),
                aw=jnp.exp(-dt / params["tau_w"]))


def integrate_currents(i_exc, i_inh, i_syn_exc, i_syn_inh, decays: Dict):
    """One dt of the synaptic-current states: exponential kernels, pulses
    add instantaneously. This recurrence is independent of the membrane
    state, so the blocked backend hoists it into a cheap window-wide scan
    (``repro.kernels.neuron_scan``) — the op tree per step is identical to
    the inline computation ``step`` used to do, keeping results bit-exact.
    """
    return (i_exc * decays["de"] + i_syn_exc,
            i_inh * decays["di"] + i_syn_inh)


def membrane_step(v, w, refrac, i_drive, params: Dict, dt: float,
                  adex: bool = True, decays: Dict = None):
    """The sequential membrane core of one dt step.

    ``i_drive`` is the already-integrated net synaptic current
    ``i_exc - i_inh`` (see ``integrate_currents``). Returns
    ``(v, w, refrac, spikes_f32)`` — the op trees are exactly the ones
    ``step`` always computed, so every caller (oracle scan, blocked ref,
    Pallas neuron_scan kernel) produces bit-identical trajectories.
    """
    g_l = params["g_leak"]
    i_total = i_drive - w

    # exponential escape current (clamped like the saturating circuit)
    if adex:
        arg = jnp.clip((v - params["v_thres"]) / params["delta_t"],
                       -20.0, 3.0)
        i_exp = g_l * params["delta_t"] * jnp.exp(arg)
    else:
        i_exp = 0.0

    v_inf = params["e_leak"] + (i_total + i_exp) / g_l
    v_new = v_inf + (v - v_inf) * decays["alpha"]

    # adaptation (exponential Euler towards a(V - E_L))
    w_inf = params["a"] * (v - params["e_leak"])
    w_new = w_inf + (w - w_inf) * decays["aw"]

    # refractory clamp
    in_refrac = refrac > 0.0
    v_new = jnp.where(in_refrac, params["e_reset"], v_new)
    w_new = jnp.where(in_refrac, w, w_new)

    # spike detection: threshold crossing ends the integration step
    spike_v = params["v_thres"] + jnp.where(adex, 2.0 * params["delta_t"], 0.0)
    spikes = (v_new > spike_v) & ~in_refrac
    v_new = jnp.where(spikes, params["e_reset"], v_new)
    w_new = jnp.where(spikes, w_new + params["b"], w_new)
    refrac = jnp.where(spikes, params["tau_refrac"],
                       jnp.maximum(refrac - dt, 0.0))
    return v_new, w_new, refrac, spikes.astype(jnp.float32)


def step(state: NeuronState, i_syn_exc, i_syn_inh, params: Dict, dt: float,
         adex: bool = True, decays: Dict = None):
    """One dt step. i_syn_*: charge injected this step [pA*us / us = pA].

    Returns (new_state, spikes[...,N] float32 in {0,1}).
    """
    if decays is None:
        decays = decay_factors(params, dt)
    i_exc, i_inh = integrate_currents(state.i_exc, state.i_inh,
                                      i_syn_exc, i_syn_inh, decays)
    v, w, refrac, spikes = membrane_step(
        state.v, state.w, state.refrac, i_exc - i_inh, params, dt,
        adex=adex, decays=decays)
    new = NeuronState(v=v, w=w, i_exc=i_exc, i_inh=i_inh, refrac=refrac)
    return new, spikes
