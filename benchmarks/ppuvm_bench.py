"""PPU-VM executor ladder vs the fixed-function R-STDP path.

Three levels:

  * rule-only executor ladder: `VectorUnit.apply_rstdp_program` under
    every executor (scan interpreter, trace-time specializer, Pallas tile
    VM) vs `ppu_update.rstdp_update_ref` (one fused jnp expression) on
    full-size [256, 512] synapse arrays — the raw cost of
    programmability per executor. The ISSUE-3 acceptance bar is
    specialized <= 1.5x the fixed-function path (from 5.3x for the scan
    interpreter in PR 2).
  * in-scan: the §5 experiment's scanned training with
    ``rule_impl="vm"`` per executor vs ``"python"`` — what the overhead
    amounts to once the emulation window dominates the trial.

The Pallas executor is timed in its native mode on TPU and in
kernel-interpret mode elsewhere; interpret mode measures semantics, not
speed, so it is reported but excluded from the acceptance comparison.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np


def _time(f, *args, iters=20):
    out = f(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def run():
    """Executor ladder + in-scan comparison, with the specializer-cache
    stats delta for the whole bench surfaced in the result (satellite of
    the PR 7 observability work: a cache that silently thrashes shows up
    as a specializer that silently got 64x slower)."""
    from repro.obs.timing import CacheDelta, eviction_storm

    with CacheDelta(warn=False) as cd:
        res = _run_inner()
    res["specialize_cache"] = dict(cd.delta)
    storm = eviction_storm(cd.delta)
    res["cache_eviction_storm"] = storm
    print(f"specializer cache over this bench: {cd.delta['hits']} hits / "
          f"{cd.delta['misses']} misses / {cd.delta['evictions']} "
          f"evictions (size {cd.delta['size']}/{cd.delta['max_size']})")
    if storm:
        print("WARNING: eviction storm — the program working set exceeds "
              "the LRU capacity; every upload re-specializes")
    return res


def _run_inner():
    from repro.configs.bss2 import BSS2
    from repro.core.anncore import AnnCore
    from repro.core.ppu import VectorUnit
    from repro.ppuvm import programs
    from repro.verif.mismatch import sample_instance

    # -- rule-only: full-size array, executor ladder vs fused update ------
    cfg = BSS2  # 256 x 512
    inst = sample_instance(cfg, jax.random.PRNGKey(0))
    ppu = VectorUnit(cfg, inst)
    core = AnnCore(cfg, inst)
    st = core.init_state()
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    st = st._replace(
        syn=st.syn._replace(weights=jax.random.randint(
            ks[0], (cfg.n_rows, cfg.n_cols), 0, 64, jnp.int8)),
        corr=st.corr._replace(
            a_causal=jax.random.uniform(ks[1], (cfg.n_rows, cfg.n_cols),
                                        maxval=8.0),
            a_acausal=jax.random.uniform(ks[2], (cfg.n_rows, cfg.n_cols),
                                         maxval=8.0)))
    reward = (jax.random.uniform(ks[3], (cfg.n_cols,)) < 0.5
              ).astype(jnp.float32)
    rs = dict(mean_reward=jnp.zeros(cfg.n_cols), key=jax.random.PRNGKey(2))
    prog = jnp.asarray(programs.rstdp_program(eta=0.5))

    # the tile-VM kernel does not lower on TPU (kernels/ppuvm_exec/ops.py)
    on_tpu = jax.default_backend() == "tpu"
    executors = ("scan", "specialized") + (() if on_tpu
                                           else ("pallas_interpret",))

    f_fixed = jax.jit(lambda s, r: ppu.apply_rstdp(
        s, dict(rs), reward=r, eta=0.5, impl="ref"))
    t_fixed = _time(f_fixed, st, reward)

    ladder = {}
    for ex in executors:
        f = jax.jit(lambda s, r, _ex=ex: ppu.apply_rstdp_program(
            s, dict(rs), reward=r, program=prog, executor=_ex))
        iters = 3 if ex == "pallas_interpret" else 20
        ladder[ex] = _time(f, st, reward, iters=iters)

    # -- in-scan: whole §5 experiment, python rule vs VM executors --------
    from repro.core.hybrid import RSTDPConfig, make_experiment, \
        make_scanned_training

    n_trials = 50
    ecfg = RSTDPConfig()
    t_scan = {}
    scan_variants = [("python", "python", "auto"),
                     ("vm", "vm", "specialized"),
                     ("vm_scan", "vm", "scan")]
    for label, impl, vex in scan_variants:
        init, trial, meta = make_experiment(
            ecfg=ecfg, instance_key=jax.random.PRNGKey(0), rule_impl=impl,
            vm_executor=vex)
        scanned = make_scanned_training(meta["scanned_training"])
        stims = jnp.asarray(np.resize([1, 2, 0], n_trials), jnp.int32)

        def once(scanned=scanned, init=init, stims=stims):
            state, hist = scanned(init(jax.random.PRNGKey(1)), stims)
            return hist["mean_reward"]

        t_scan[label] = _time(once, iters=5) / n_trials

    executor_ladder = dict(
        fixed_us=t_fixed * 1e6,
        **{f"{ex}_us": t * 1e6 for ex, t in ladder.items()},
        **{f"{ex}_overhead_x": t / t_fixed for ex, t in ladder.items()},
    )
    res = dict(
        name="ppuvm",
        executor_ladder=executor_ladder,
        rule_fixed_us=t_fixed * 1e6,
        rule_vm_us=ladder["scan"] * 1e6,
        rule_overhead_x=ladder["scan"] / t_fixed,
        rule_specialized_overhead_x=ladder["specialized"] / t_fixed,
        trial_python_us=t_scan["python"] * 1e6,
        trial_vm_us=t_scan["vm"] * 1e6,
        trial_vm_scan_us=t_scan["vm_scan"] * 1e6,
        trial_overhead_x=t_scan["vm"] / t_scan["python"],
        n_instructions=int(prog.shape[0]),
    )
    print(f"rule-only [256x512] vs fixed {res['rule_fixed_us']:.0f}us "
          f"({res['n_instructions']} instructions):")
    for ex, t in ladder.items():
        note = "  (interpret: semantics-only)" if ex == "pallas_interpret" \
            else ""
        print(f"  {ex:<17} {t * 1e6:9.0f}us  {t / t_fixed:6.2f}x{note}")
    print(f"in-scan trial [{ecfg.n_inputs}->{ecfg.n_neurons}]: "
          f"python {res['trial_python_us']:.0f}us  "
          f"VM/specialized {res['trial_vm_us']:.0f}us "
          f"({res['trial_overhead_x']:.2f}x)  "
          f"VM/scan {res['trial_vm_scan_us']:.0f}us "
          f"({t_scan['vm_scan'] / t_scan['python']:.2f}x)")
    return res


if __name__ == "__main__":
    run()
