"""The inter-chip event router: one window of bus traffic per call.

Spikes produced on one chip in window ``t`` become input row events on
connected chips in window ``t+1`` — a one-window routing-latency budget,
matching the hardware's inter-chip bus delay. Per window the router

  1. projects each chip's output spikes onto its out-links' route tables
     (per-link [T, R] delivery grids; several routes landing on the same
     ``(t, dst_row)`` slot merge by ``max`` — one physical event per
     driver slot, order-independent and exact);
  2. censuses each link against the per-link event budget (the shared
     ``events.census_fits`` predicate — the same gate as the sparse
     synaptic path, including the per-step bandwidth axis);
  3. exchanges the grids between chips: as compact ``(t, row, addr,
     efficacy)`` ``EventStream`` records (``link_mode="compact"``), as
     the dense grids (``"dense"``), or census-gated between the two
     (``"auto"`` — compact while every link fits, whole-exchange dense
     fallback otherwise, the PR 6 fallback idiom). Overflow is counted in
     telemetry (``count_links``), never silent: compact over budget
     DROPS tail records (visible divergence + counter), auto falls back
     (bit-exact + counter).

Transports: with no mesh (or an instance rule the link collectives
cannot run over) everything is local jnp — the math core. With a mesh
whose single instance axis evenly divides the chip count, the exchange
runs under ``shard_map``: ``ppermute`` moves the one boundary-crossing
link of each device for the ring topology, a masked ``all_gather``
realises arbitrary fan-in for all2all. Both transports are bit-identical
to the local one (asserted in ``tests/test_wafer.py`` on the forced
multi-device CPU).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import events
from repro.faults import inject as finject
from repro.obs import trace as obs_trace
from repro.wafer.topology import WaferPlan

class InterChipRouter:
    """Constant route tables + the per-window routing step.

    ``link_budget`` / ``link_step_budget``: static per-link stream
    capacity and per-step bandwidth (defaults: the density-derived
    ``events.default_max_events`` and the no-constraint ``R``).
    ``link_mode``: "auto" (default) | "compact" | "dense".
    ``ctx``: optional ``ShardingCtx`` — enables the shard_map transport
    when its instance rule is a single mesh axis that evenly divides the
    chip count; anything else degrades to the local transport (the same
    graceful degradation as ``ShardingCtx._pspec``).
    ``faults``: a ``repro.faults`` overlay — dead links deliver nothing,
    flaky links drop a deterministic hash-selected fraction of events
    (identical under the local and shard_map transports). ``None`` is
    the identity (no extra ops).

    When the plan carries FORWARD rules (``reroute_plan`` failover),
    pass last window's delivered grid as ``route(..., routed_in=...)``:
    each forwarding chip re-transmits the events its relay row received,
    so rerouted traffic lands one window after the direct route would
    have — and is counted in the ``link_reroutes`` telemetry counter.

    Args:
      plan: a validated ``WaferPlan`` (route + forward tables become
        constant index arrays at construction).
      ctx: optional ``ShardingCtx`` (see above).
      link_budget / link_step_budget: compact-transport capacities
        (see above).
      link_mode: "auto" | "compact" | "dense" (see above).
      faults: ``FaultPlan`` link overlay, or ``None`` (see above).

    Per window, ``route(out_spikes_t, telemetry=, routed_in=)`` turns
    [T, K, C] spikes into the next window's [T, K, R] delivery grid and
    ``merge(routed_ev, ext_ev, ext_addr)`` folds a delivery grid into
    the external inputs (scatter-max — order-independent because routed
    and external events on one row carry the same address).

    Contract pointers: tests/test_wafer.py (split == monolithic,
    overflow counted never silent, transports interchangeable),
    tests/test_faults.py (link faults), tests/test_mapper.py (mapper
    round trips run every window through this router).
    """

    def __init__(self, plan: WaferPlan, ctx=None,
                 link_budget: Optional[int] = None,
                 link_step_budget: Optional[int] = None,
                 link_mode: str = "auto", faults=None):
        if link_mode not in ("auto", "compact", "dense"):
            raise ValueError(f"unknown link_mode {link_mode!r}")
        self.plan = plan
        self.link_mode = link_mode
        self.link_budget = link_budget
        self.link_step_budget = link_step_budget
        self.faults = faults
        topo = plan.topology
        self.K, self.R, self.C = topo.n_chips, plan.n_rows, plan.n_cols
        links = topo.links()
        self.L = len(links)

        # ragged per-link route tables, padded to the max route count per
        # link; padded slots gather column 0 and scatter into the dropped
        # R slot, so they contribute nothing
        per_link = {l: [] for l in range(self.L)}
        link_id = {sd: l for l, sd in enumerate(links)}
        for i in range(plan.n_routes):
            l = link_id[(int(plan.src_chip[i]), int(plan.dst_chip[i]))]
            per_link[l].append((int(plan.src_col[i]), int(plan.dst_row[i])))
        m = max((len(v) for v in per_link.values()), default=0)
        self.M = max(m, 1)
        src = np.zeros((self.L, self.M), np.int32)
        dst = np.full((self.L, self.M), self.R, np.int32)
        for l, v in per_link.items():
            for j, (sc, dr) in enumerate(v):
                src[l, j], dst[l, j] = sc, dr
        self.link_src = jnp.asarray(src)
        self.link_dst = jnp.asarray(dst)
        self.link_from = jnp.asarray([s for s, _ in links])
        self.link_to = jnp.asarray([d for _, d in links])
        # forward tables (failover hops): same padded-ragged layout, but
        # the gather source is a ROW of the previous window's delivered
        # grid instead of a spike column
        per_fwd = {l: [] for l in range(self.L)}
        for i in range(plan.n_forwards):
            l = link_id[(int(plan.fwd_src_chip[i]),
                         int(plan.fwd_dst_chip[i]))]
            per_fwd[l].append((int(plan.fwd_src_row[i]),
                               int(plan.fwd_dst_row[i])))
        mf = max((len(v) for v in per_fwd.values()), default=0)
        self.MF = max(mf, 1)
        fsrc = np.zeros((self.L, self.MF), np.int32)
        fdst = np.full((self.L, self.MF), self.R, np.int32)
        for l, v in per_fwd.items():
            for j, (sr, dr) in enumerate(v):
                fsrc[l, j], fdst[l, j] = sr, dr
        self.fwd_src = jnp.asarray(fsrc)
        self.fwd_dst = jnp.asarray(fdst)
        # per-link delivery address grid (addresses ride with the records)
        ag = np.zeros((self.L, self.R), np.int8)
        for i in range(plan.n_routes):
            l = link_id[(int(plan.src_chip[i]), int(plan.dst_chip[i]))]
            ag[l, int(plan.dst_row[i])] = np.int8(plan.addr[i])
        for i in range(plan.n_forwards):
            l = link_id[(int(plan.fwd_src_chip[i]),
                         int(plan.fwd_dst_chip[i]))]
            ag[l, int(plan.fwd_dst_row[i])] = np.int8(plan.fwd_addr[i])
        self.link_addr = jnp.asarray(ag)
        # receiver-side planes for merge()
        self.dst_addr = jnp.asarray(plan.dst_addr_grid())      # [K, R] int8

        # sharded transport: a single instance mesh axis that evenly
        # divides the chip count — else local transport
        self._axis = None
        self._dp = 1
        if ctx is not None and ctx.mesh is not None:
            axis = ctx.instance_axis_name()
            dp = ctx.dp_size
            if axis is not None and dp > 1 and self.K % dp == 0:
                self._axis = axis
                self._dp = dp
                self._mesh = ctx.mesh
                self._spec_in, self._spec_rep = ctx.link_specs(1, 3)

    # -- static helpers ------------------------------------------------------
    def _budgets(self, T: int) -> Tuple[int, int]:
        b = self.link_budget
        if b is None:
            b = events.default_max_events(T, self.R, 0.05)
        s = self.link_step_budget
        if s is None:
            s = self.R
        return b, min(s, self.R)

    def init_buffer(self, T: int) -> jnp.ndarray:
        """The routed-event carry: [T, K, R] delivery grid (what last
        window's spikes deposit for this window). Starts silent."""
        return jnp.zeros((T, self.K, self.R), jnp.float32)

    # -- chip-local math core ------------------------------------------------
    def _link_grids(self, out_l, link_src, link_dst):
        """[T, Lx, C] per-link source spikes -> [T, Lx, R] delivery grids
        (scatter-max over routes; duplicate (t, row) targets merge)."""
        T, Lx = out_l.shape[0], out_l.shape[1]
        vals = jnp.take_along_axis(out_l, link_src[None], axis=-1)
        l_idx = jnp.arange(Lx)[:, None]
        return jnp.zeros((T, Lx, self.R + 1), jnp.float32).at[
            :, l_idx, link_dst].max(vals)[..., :self.R]

    @staticmethod
    def _census(grids):
        """[T, Lx, R] -> per-link (event count, worst per-step count)."""
        fired = (grids != 0.0).astype(jnp.int32)
        per_step = jnp.sum(fired, axis=-1)                 # [T, Lx]
        return jnp.sum(per_step, axis=0), jnp.max(per_step, axis=0)

    def _pack(self, grids, link_addr, T, budget, step_budget):
        """[T, Lx, R] grids -> per-link EventStream ([Lx, E] leaves)."""
        g = jnp.moveaxis(grids, 1, 0)                      # [Lx, T, R]
        ad = jnp.broadcast_to(link_addr[:, None, :].astype(jnp.int32),
                              g.shape)
        st = events.pack_events_batch(g, ad, budget)
        if step_budget < self.R:
            st = events.truncate_stream(st, T, step_budget)
        return st

    def _unpack(self, st, T):
        ev, _ = events.unpack_events_batch(st, T, self.R)
        return jnp.moveaxis(ev, 0, 1)                      # [T, Lx, R]

    # -- local transport -----------------------------------------------------
    def _route_local(self, out, T, budget, step_budget, routed_in=None):
        grids = self._link_grids(out[:, self.link_from], self.link_src,
                                 self.link_dst)
        n_fwd = None
        if routed_in is not None:
            # failover hops: re-transmit what the relay rows received last
            # window; merged BEFORE census so the bus budget covers the
            # rerouted traffic too
            fgrids = self._link_grids(routed_in[:, self.link_from],
                                      self.fwd_src, self.fwd_dst)
            n_f, _ = self._census(fgrids)
            n_fwd = jnp.sum(n_f)
            grids = jnp.maximum(grids, fgrids)
        grids = finject.links(self.faults, grids, np.arange(self.L))
        n, kmax = self._census(grids)
        fits = events.census_fits(n, kmax, budget, step_budget)

        def compact():
            return self._unpack(
                self._pack(grids, self.link_addr, T, budget, step_budget), T)

        if self.link_mode == "dense":
            delivered = grids
        elif self.link_mode == "compact":
            delivered = compact()
        else:
            delivered = jax.lax.cond(jnp.all(fits), compact, lambda: grids)
        routed = jnp.zeros((T, self.K, self.R), jnp.float32).at[
            :, self.link_to, :].max(delivered)
        return routed, n, fits, n_fwd

    # -- shard_map transports ------------------------------------------------
    def _route_sharded(self, out, T, budget, step_budget, routed_in=None):
        axis, dp = self._axis, self._dp
        K_loc = self.K // dp
        L_loc = self.L // dp
        perm = [(d, (d + 1) % dp) for d in range(dp)]
        ring = self.plan.topology.kind == "ring"
        use_fwd = routed_in is not None
        # local link -> local source chip is static (links are src-major
        # with one uniform out-link block per chip)
        lf_loc = (jnp.arange(L_loc) if ring
                  else jnp.arange(L_loc) // self.K)

        def exch_leaf(x):
            # ring: only the last local link crosses the device boundary
            recv = jax.lax.ppermute(x[K_loc - 1:K_loc], axis, perm)
            return jnp.concatenate([recv, x[:K_loc - 1]], axis=0)

        def exch_stream(st):
            st = st._replace(valid=st.valid.astype(jnp.int8))
            st = jax.tree.map(exch_leaf, st)
            return st._replace(valid=st.valid.astype(bool))

        def body(out_loc, *rest):
            rank = jax.lax.axis_index(axis)
            l0 = rank * L_loc
            lsrc = jax.lax.dynamic_slice_in_dim(self.link_src, l0, L_loc)
            ldst = jax.lax.dynamic_slice_in_dim(self.link_dst, l0, L_loc)
            laddr = jax.lax.dynamic_slice_in_dim(self.link_addr, l0, L_loc)
            grids = self._link_grids(out_loc[:, lf_loc], lsrc, ldst)
            n_fwd = None
            if use_fwd:
                fsrc = jax.lax.dynamic_slice_in_dim(self.fwd_src, l0, L_loc)
                fdst = jax.lax.dynamic_slice_in_dim(self.fwd_dst, l0, L_loc)
                fgrids = self._link_grids(rest[0][:, lf_loc], fsrc, fdst)
                n_f, _ = self._census(fgrids)
                n_fwd = jax.lax.psum(jnp.sum(n_f), axis)
                grids = jnp.maximum(grids, fgrids)
            # absolute link ids keep the flaky-drop hash identical to the
            # local transport's
            grids = finject.links(self.faults, grids,
                                  l0 + jnp.arange(L_loc))
            n_loc, k_loc = self._census(grids)
            n = jax.lax.psum(jax.lax.dynamic_update_slice(
                jnp.zeros((self.L,), jnp.int32), n_loc, (l0,)), axis)
            k = jax.lax.psum(jax.lax.dynamic_update_slice(
                jnp.zeros((self.L,), jnp.int32), k_loc, (l0,)), axis)
            fits = events.census_fits(n, k, budget, step_budget)

            if ring:
                def dense():
                    # payload j is the in-link of local chip j after the
                    # rotation; ring fan-in is 1, so it IS the slab
                    return jnp.moveaxis(exch_leaf(
                        jnp.moveaxis(grids, 1, 0)), 0, 1)

                def compact():
                    st = self._pack(grids, laddr, T, budget, step_budget)
                    return self._unpack(exch_stream(st), T)
            else:
                def _deliver(delivered_all):
                    routed = jnp.zeros((T, self.K, self.R),
                                       jnp.float32).at[
                        :, self.link_to, :].max(delivered_all)
                    return jax.lax.dynamic_slice_in_dim(
                        routed, rank * K_loc, K_loc, axis=1)

                def dense():
                    return _deliver(jax.lax.all_gather(
                        grids, axis, axis=1, tiled=True))

                def compact():
                    st = self._pack(grids, laddr, T, budget, step_budget)
                    st = st._replace(valid=st.valid.astype(jnp.int8))
                    st = jax.tree.map(lambda x: jax.lax.all_gather(
                        x, axis, axis=0, tiled=True), st)
                    st = st._replace(valid=st.valid.astype(bool))
                    return _deliver(self._unpack(st, T))

            if self.link_mode == "dense":
                routed_loc = dense()
            elif self.link_mode == "compact":
                routed_loc = compact()
            else:
                routed_loc = jax.lax.cond(jnp.all(fits), compact, dense)
            if use_fwd:
                return routed_loc, n, fits, n_fwd
            return routed_loc, n, fits

        n_out = 4 if use_fwd else 3
        in_specs = (self._spec_in,) * (2 if use_fwd else 1)
        out_specs = (self._spec_in,) + (self._spec_rep,) * (n_out - 1)
        fn = jax.shard_map(body, mesh=self._mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        res = fn(out, routed_in) if use_fwd else fn(out)
        return res if use_fwd else (*res, None)

    # -- public API ----------------------------------------------------------
    def route(self, out_spikes_t, telemetry=None, routed_in=None):
        """[T, K, C] window output spikes -> ([T, K, R] delivery grid for
        the NEXT window, updated telemetry). ``routed_in`` (last window's
        delivered grid) feeds the plan's forward rules — required for
        failover plans, ignored when the plan has none."""
        T = out_spikes_t.shape[0]
        budget, step_budget = self._budgets(T)
        if self.plan.n_forwards == 0:
            routed_in = None
        elif routed_in is None:
            raise ValueError("this plan has forward rules: route() needs "
                             "routed_in (last window's delivered grid)")
        if self._axis is not None:
            routed, n, fits, n_fwd = self._route_sharded(
                out_spikes_t, T, budget, step_budget, routed_in)
        else:
            routed, n, fits, n_fwd = self._route_local(
                out_spikes_t, T, budget, step_budget, routed_in)
        telemetry = obs_trace.count_links(telemetry, n, fits)
        telemetry = obs_trace.count_reroutes(telemetry, n_fwd)
        return routed, obs_trace.count_faults(telemetry, self.faults)

    def link_census(self, out_spikes_t):
        """[L] delivered-event counts per link for one window of spikes —
        the screening probe's observable. Includes the fault hook (what
        the bus ACTUALLY delivers), excludes forward traffic and budget
        gating (raw capacity census)."""
        grids = self._link_grids(out_spikes_t[:, self.link_from],
                                 self.link_src, self.link_dst)
        grids = finject.links(self.faults, grids, np.arange(self.L))
        n, _ = self._census(grids)
        return n

    def merge(self, routed_ev, ext_ev, ext_addr):
        """Deliver last window's routed grid into this window's inputs.

        Events merge by ``max`` (a routed and an external event on the
        same (t, row) slot are one physical driver event); on slots where
        a routed event lands, the row's (validated-unique) route address
        wins over the external address — deterministic and identical on
        every chip count, which is what the split-vs-monolithic contract
        needs."""
        if self.plan.n_deliveries == 0:
            return ext_ev, ext_addr
        ev = jnp.maximum(ext_ev, routed_ev)
        addr = jnp.where(routed_ev > 0.0, self.dst_addr,
                         ext_addr.astype(jnp.int8))
        return ev, addr


def run_windows(core, router: InterChipRouter, state, ev_w, ad_w,
                telemetry=None):
    """Scan W routed windows: ``ev_w``/``ad_w`` are [W, T, K, R] external
    inputs; each window's spikes are routed into the next window's inputs
    (one-window latency). Returns ``(state, dict(spikes=[W, T, K, C],
    routed=last grid, telemetry=...))``."""
    T = ev_w.shape[1]

    def body(carry, xs):
        st, routed, tele = carry
        ev, ad = xs
        st, out = core.run_routed(st, routed, ev, ad, router,
                                  telemetry=tele)
        return ((st, out["routed"], out.get("telemetry")),
                out["spikes"])

    (state, routed, tele), spikes = jax.lax.scan(
        body, (state, router.init_buffer(T), telemetry), (ev_w, ad_w))
    return state, dict(spikes=spikes, routed=routed, telemetry=tele)
