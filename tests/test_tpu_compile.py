"""The main path's kernels, compiled for a TPU v5e that is described, not
attached.

The TPU compiler ships with the installed jaxlib, so each Pallas kernel
of the default TPU path (``backend="blocked"``, ``kernel_impl="pallas"``)
is compiled here at the silicon's full size (256 rows x 512 columns,
T = 128) and at the paper's §5 size (32 x 16). Nothing runs: these tests
catch what interpret mode cannot (block shapes against the lane rule,
Mosaic lowering gaps), not wrong results.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.bss2 import BSS2

T = 128
# (rows, columns) of one chip: full silicon and the paper's §5 chip
SIZES = {"full": (256, 512), "paper": (32, 16)}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, args, sharding):
    specs = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        args)
    return jax.jit(fn).lower(*specs).compile().as_text()


def _kernel_case(name, R, C, N=2):
    """(fn, argument shapes) for one kernel on an N-instance fleet of R x C
    chips, with the operand shapes the blocked backend hands it (the
    synaptic phase sees one Dale half, R/2 rows)."""
    S = jax.ShapeDtypeStruct
    f32, i8, i32 = jnp.float32, jnp.int8, jnp.int32
    half = R // 2
    if name == "synray":
        from repro.kernels.synray.kernel import synaptic_current_pallas
        return (lambda e, a, w, s: synaptic_current_pallas(e, a, w, s),
                (S((N, T, half), f32), S((N, T, half), i8),
                 S((N, half, C), i8), S((N, half, C), i8)))
    if name == "synray_sparse":
        # the sparse path's TPU form (its jnp twin: the kernel's gather
        # does not lower), reached the way the blocked backend reaches it
        from repro.core.synapse import synaptic_current_window
        return (lambda w, a, e, d, g: synaptic_current_window(
                    w, a, e, d, g, impl="pallas", const_addr=True,
                    sparse="always"),
                (S((N, half, C), i8), S((N, half, C), i8),
                 S((T, N, half), f32), S((T, N, half), i8), S((N, C), f32)))
    if name == "corr":
        from repro.kernels.corr.kernel import correlation_window_pallas
        return (lambda p, q, a, b, c, d: correlation_window_pallas(
                    p, q, a, b, c, d, lam=0.9),
                (S((N, T, R), f32), S((N, T, C), f32), S((N, R), f32),
                 S((N, C), f32), S((N, R, C), f32), S((N, R, C), f32)))
    if name == "neuron_scan":
        from repro.kernels.neuron_scan.kernel import neuron_window_pallas
        return (lambda a, b, c, d: neuron_window_pallas(
                    a, b, c, d, dt=BSS2.dt, use_adex=True, T=T),
                (S((N, T, C), f32), S((N, T, C), f32), S((N, 6, C), f32),
                 S((N, 12, C), f32)))
    if name == "ppu_update":
        from repro.kernels.ppu_update.kernel import rstdp_update_pallas
        return (lambda w, a, b, o, g, m, x: rstdp_update_pallas(
                    w, a, b, o, g, m, x, eta=1.0),
                (S((R, C), i8), S((R, C), f32), S((R, C), f32), S((C,), f32),
                 S((C,), f32), S((C,), f32), S((R, C), f32)))
    raise KeyError(name)


@pytest.mark.parametrize("size", sorted(SIZES))
@pytest.mark.parametrize("kernel", ["synray", "synray_sparse", "corr",
                                    "neuron_scan", "ppu_update"])
def test_kernel_compiles_for_v5e(one_chip, kernel, size):
    R, C = SIZES[size]
    fn, args = _kernel_case(kernel, R, C)
    text = _compile_text(fn, args, one_chip)
    if kernel != "synray_sparse":
        assert "tpu_custom_call" in text, f"{kernel}: no Mosaic kernel"


def test_full_size_blocked_trial_compiles_for_v5e(one_chip):
    """One §5 trial of a 2-chip fleet at full size on the TPU path: every
    kernel of the window is a Mosaic custom call in the compiled trial."""
    from repro.core.hybrid import RSTDPConfig, make_experiment

    R, C = SIZES["full"]
    cfg = dataclasses.replace(BSS2, n_rows=R, n_cols=C, n_neurons=C)
    ecfg = RSTDPConfig(n_inputs=R // 2, n_neurons=C, pattern_size=24,
                       trial_steps=T)
    init, trial, _ = make_experiment(cfg=cfg, ecfg=ecfg, prefix=(2,),
                                     backend="blocked", kernel_impl="pallas")
    state = jax.eval_shape(init, jax.random.PRNGKey(0))
    stim = jax.ShapeDtypeStruct((), jnp.int32)
    text = _compile_text(trial, (state, stim), one_chip)
    # two synray halves, the neuron window and the correlation window
    assert text.count("tpu_custom_call") >= 4, text.count("tpu_custom_call")
