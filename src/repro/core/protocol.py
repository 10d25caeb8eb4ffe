"""Event-driven cross-region protocol engines: DiLoCo, Streaming DiLoCo, CoCoDC.

The engine is a THIN HOST WRAPPER: all device state (global model theta^g,
outer Nesterov momentum, the fixed-capacity in-flight fragment buffers, the
adaptive-transmission rates, the availability mask) lives in a single
`EngineState` pytree (core/engine_state.py), and every device mutation is one
pure, jit-compiled transition call. The wrapper owns only host-side scalars:
the simulated WAN wall-clock, WAN-channel queueing, per-link traffic matrices,
and the deterministic schedule of WHICH fragment goes WHEN.

Timeline semantics (faithful to the paper):
  * every local step costs T_c;
  * DiLoCo: at t % H == H-1, a BLOCKING full-model all-reduce (wall += T_s_full),
    outer update, and all workers restart from theta^g;
  * Streaming DiLoCo: fragment p's all-reduce is initiated on a fixed round-robin
    schedule (one fragment every H/K steps); on completion: outer update of the
    fragment, then Eq. 3 blending;
  * CoCoDC: initiations every h = H/N steps (Eq. 9/10), fragment chosen by
    Algorithm 2; local fragment snapshot taken at initiation; on completion:
    outer update, then Algorithm 1 delay compensation; R_p updated (Eq. 11).

Delivery times are DERIVED, not fixed: a fragment initiated at step t completes
at the simulated transfer finish time — queueing behind earlier transfers when
all `Topology.concurrent_collectives` WAN channels are busy, and paced by the
slowest inter-region link of the collective (ring or hierarchical). Under the
symmetric paper-calibrated network with a free channel this reduces exactly to
the paper's `t + tau`.

When the topology carries a `LinkDynamics` layer, a transfer's completion is
the time-INTEGRAL of the bottleneck bandwidth factor (diurnal troughs, outage
windows with retry, seeded per-transfer jitter) — see
`Topology.transfer_time`. The engine then also accounts `stall_seconds` (time
lost vs the nominal static cost) and `n_retries`, and owns the jitter draw
counter so checkpoint/resume replays the identical transfer schedule.
`dynamics=None` follows the original static arithmetic bitwise (pinned by
tests/test_network_dynamics.py).

With `CoCoDCConfig.routing="routed"` every collective executes over a
`CommPlan` from the deterministic `RoutePlanner` (core/network.py): multi-hop
min-cost routes over the CURRENT link state, re-planned whenever a
`LinkDynamics.next_change` edge passes, with optional hub failover
(`hub_failover=True`: dark regions drop out of the collective and the
next-best-connected region stands in as hub until recovery). The Algorithm-2
cost vector is refreshed from the active plan on every re-plan, and
`adaptive_resync=True` re-derives Eq. 9's N / Eq. 10's h once per outer round
from the measured durations of completed transfers. `routing="static"`
(default) keeps every pre-routing code path — and the PR 3 golden delivery
schedules — bitwise.

The cross-pod mean over the worker axis is the ONLY cross-region collective;
under the multi-pod mesh it lowers to an all-reduce over the `pod` axis
(verified in the dry-run).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import CoCoDCConfig
from repro.core import adaptive as adaptive_lib
from repro.core import engine_state as es
from repro.core.fragments import Fragmenter
from repro.core.methods import get_method
from repro.core.network import (CommPlan, FairShareSim, RoutePlanner, Topology,
                                as_topology)
from repro.telemetry import Counters, span

# Host-scheduler checkpoint schema. One upgrade path
# (`upgrade_scheduler_state`) replaces the `.get(...)`-default sprawl that
# accumulated as PRs added fields:
#   v1 (PR 2) — pending/seq/channel clocks/traffic matrices only
#   v2 (PR 3) — + dynamics clocks (dyn_seq, stall_seconds, n_retries)
#   v3 (PR 4) — + routing/resync blocks, 6-element pending rows (duration)
#   v4 (PR 5) — + explicit schema_version stamp
#   v5 (PR 6) — + wire_bytes_raw (uncompressed payload tally for the
#               wire-codec compression ratio)
#   v6 (PR 7) — + fair-share traffic plane: 8-element pending rows (wire
#               bytes + transfer id), per-transfer sojourn log, in-flight
#               fair-share flow set, per-sample bytes in the resync window,
#               multipath split counter
SCHEDULER_SCHEMA_VERSION = 6

_ROUTING_DEFAULTS = {"plan_time": -1.0, "counted_time": -1.0, "plan_dark": [],
                     "reroutes": 0, "hub_elections": 0}
# N/h None = "keep the engine-derived cadence" (pre-routing checkpoints)
_RESYNC_DEFAULTS = {"measured": [], "measured_bytes": [], "N": None,
                    "h_cocodc": None}


def upgrade_scheduler_state(st: Dict[str, object]) -> Dict[str, object]:
    """Upgrade a serialized host-scheduler dict of ANY prior schema version to
    the current one, filling in exactly what the writing code could not have
    known about. This is the ONLY place checkpoint back-compat defaults live;
    `restore_scheduler` reads the upgraded dict without fallbacks."""
    st = dict(st)
    # v1 -> v2: pre-dynamics checkpoints carry no dynamics clocks (static
    # runs never advance them)
    st.setdefault("dyn_seq", 0)
    st.setdefault("stall_seconds", 0.0)
    st.setdefault("n_retries", 0)
    # v2 -> v3: pre-routing checkpoints have no planner/resync state and
    # 5-element pending rows (no measured duration); v5 -> v6 extends the
    # rows with wire bytes (0 = unknown, excluded from the Eq. 9 byte fit)
    # and the transfer id (-1 = predates the transfer log)
    rows = []
    for r in st["pending"]:
        row = list(r)[:8] + [0.0] * (6 - len(r))
        if len(row) < 7:
            row.append(0)
        if len(row) < 8:
            row.append(-1)
        rows.append(row)
    st["pending"] = rows
    routing = dict(st.get("routing") or {})
    for k, v in _ROUTING_DEFAULTS.items():
        routing.setdefault(k, v)
    st["routing"] = routing
    resync = dict(st.get("resync") or {})
    for k, v in _RESYNC_DEFAULTS.items():
        resync.setdefault(k, v)
    # pre-v6 windows carry durations without payload sizes; pad with zeros so
    # the decomposed fit skips them instead of mispairing
    if len(resync["measured_bytes"]) != len(resync["measured"]):
        resync["measured_bytes"] = [0.0] * len(resync["measured"])
    st["resync"] = resync
    # v4 -> v5: pre-codec checkpoints never tracked the raw (uncompressed)
    # payload tally; defaulting it to bytes_sent resumes with ratio 1.0 and
    # lets the tally diverge from there
    st.setdefault("wire_bytes_raw", st["bytes_sent"])
    # v5 -> v6: fair-share traffic plane (serial checkpoints carry no flow
    # set; the sojourn log starts empty and fills from resume onward)
    st.setdefault("multipath_splits", 0)
    st.setdefault("transfer_log", [])
    st.setdefault("fairshare", None)
    # stamp the version
    st["schema_version"] = SCHEDULER_SCHEMA_VERSION
    return st


def _percentile(sorted_vals: List[float], q: float) -> float:
    """Nearest-rank percentile of an ascending list (0.0 on empty)."""
    n = len(sorted_vals)
    if n == 0:
        return 0.0
    return float(sorted_vals[min(n - 1, max(0, math.ceil(q * n) - 1))])


@dataclasses.dataclass
class PendingSync:
    """Host-side mirror of one in-flight fragment transfer (scheduling only —
    the payload lives in EngineState.inflight_*)."""
    frag: int
    t_init: int
    deliver_at: int        # step index at which the delivery lands
    finish_time: float     # simulated transfer completion (wall seconds)
    seq: int               # initiation order (stable delivery tie-break)
    duration: float = 0.0  # measured transfer seconds (finish - channel start;
                           # queueing excluded) — the Eq. 9 re-derivation input
    wire: int = 0          # wire bytes of this transfer (the Eq. 9 byte-fit
                           # pairs with duration; 0 = pre-v6 checkpoint)
    tid: int = -1          # transfer id (fair-share flow id / sojourn-log key)


class ProtocolEngine:
    """One engine instance per training run. Device state is functional
    (`self.state`); host methods schedule transitions and account wall-clock.
    The hook and each transition are host spans (`repro.protocol`,
    `.deliver`, `.select`, `.initiate`); initiations, deliveries and blocking
    reads are counted in `telemetry`, the trainer's `Counters` when it built
    the engine."""

    def __init__(self, method: str, ccfg: CoCoDCConfig, fragmenter: Fragmenter,
                 network, params_stack, *, dc_impl: str = "ref",
                 engine_impl: str = "jit",
                 telemetry: "Counters | None" = None):
        # registry lookup — unknown names raise listing registered methods
        self.method_impl = get_method(method)
        assert engine_impl in ("jit", "host")
        self.method = method
        self.cfg = ccfg
        self.frag = fragmenter
        self.topology: Topology = as_topology(network)
        self.net = self.topology          # cost-model view (t_c / t_s)
        self.dc_impl = dc_impl
        self.engine_impl = engine_impl
        self.M = ccfg.num_workers
        self.K = ccfg.num_fragments
        self.H = ccfg.local_steps
        self.tau = ccfg.overlap_depth
        self.telemetry = Counters() if telemetry is None else telemetry

        # fused_updates stores theta_g/momentum as flat planes; keep the
        # single-model leaf shapes so the pytree views can materialize at the
        # external boundary (properties below) without touching params
        self._model_sds = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), params_stack)
        self.state = es.init_state(method, ccfg, params_stack,
                                   frag=fragmenter)
        self._fns = es.make_engine_fns(method, ccfg, fragmenter,
                                       dc_impl=dc_impl,
                                       use_jit=(engine_impl == "jit"))

        # Eq. 9/10 scheduling interval. With an active wire codec the startup
        # T_s sees the compressed payload (cheaper syncs -> more of them per
        # round); codec="none" keeps the raw-bytes arithmetic bitwise.
        mean_frag_bytes = self.frag.total_bytes / self.K
        t_s = self.topology.t_s(self._wire_bytes(int(mean_frag_bytes))
                                if ccfg.wire_codec != "none"
                                else int(mean_frag_bytes))
        self._t_s_startup = t_s
        self.N = adaptive_lib.target_syncs(self.K, self.H, self.topology.t_c,
                                           t_s, ccfg.net_utilization)
        self.h_cocodc = adaptive_lib.sync_interval(self.H, self.N)
        self.h_stream = max(1, self.H // self.K)
        # per-fragment WAN price (seconds per sync) for Algorithm 2 link-aware
        # pricing — heterogeneous fragments/links make some syncs cheaper.
        # With routing enabled this vector is refreshed from the ACTIVE plan
        # every re-plan (the startup value goes stale on dynamic links).
        self._frag_cost = [
            self.topology.t_s(self._wire_bytes(self.frag.fragment_bytes(p)))
            for p in range(self.K)]
        # partial participation (straggler tolerance, beyond-paper): offline
        # workers neither contribute to nor receive fragment syncs
        self.worker_available = [True] * self.M

        # routed communication-plan layer (off by default — the static path
        # must stay bitwise-identical to the PR 3 goldens)
        if ccfg.routing not in ("static", "routed"):
            raise ValueError(f"unknown routing mode {ccfg.routing!r} "
                             f"(options: static, routed)")
        if ccfg.hub_failover and ccfg.routing != "routed":
            raise ValueError("hub_failover requires routing='routed'")
        if ccfg.channel_scheduler not in ("serial", "fairshare"):
            raise ValueError(
                f"unknown channel_scheduler {ccfg.channel_scheduler!r} "
                f"(options: serial, fairshare)")
        if ccfg.multipath_k < 1:
            raise ValueError(f"multipath_k must be >= 1, "
                             f"got {ccfg.multipath_k}")
        if ccfg.multipath_k > 1 and ccfg.routing != "routed":
            raise ValueError("multipath_k > 1 requires routing='routed' "
                             "(k-path splitting needs the route planner)")
        self._planner: "RoutePlanner | None" = None
        if ccfg.routing == "routed":
            self._planner = RoutePlanner(
                self.topology, hub_failover=ccfg.hub_failover,
                ref_bytes=self._wire_bytes(int(mean_frag_bytes)),
                multipath_k=ccfg.multipath_k)
        self._plan: "CommPlan | None" = None
        self._plan_time: "float | None" = None
        # regions the PLANNER took offline -> the availability the USER had
        # set beforehand (restored verbatim on recovery)
        self._plan_dark: Dict[int, bool] = {}
        self.reroutes = 0                # plan changes between transfer uses
        self.hub_elections = 0           # hub changes (failover + restore)
        # counters sample plan changes at TRANSFER use only (wall-clock
        # refreshes would make them loop-cadence-dependent); the reference is
        # the last transfer-used plan, re-derivable from its plan time
        self._counted_time: "float | None" = None
        self._counted_key = None
        self._counted_hub: "int | None" = None
        # Eq. 9/10 re-derivation from measured transfer durations (methods
        # with a fixed cadence opt out via the strategy flag)
        self._resync: "adaptive_lib.ResyncState | None" = None
        if ccfg.adaptive_resync and self.method_impl.supports_adaptive_resync:
            self._resync = adaptive_lib.ResyncState()

        # host-side schedule + stats
        self.pending: List[PendingSync] = []
        self._seq = 0
        self.wall_clock = 0.0
        self.comm_seconds = 0.0
        self.bytes_sent = 0
        self.wire_bytes_raw = 0      # uncompressed (f32) payload tally
        self.n_syncs = 0
        # >= 1 is validated at Topology construction — no silent rewrite here
        self._channel_free = [0.0] * self.topology.concurrent_collectives
        m = self.M
        self.link_bytes = np.zeros((m, m), dtype=np.float64)
        self.link_seconds = np.zeros((m, m), dtype=np.float64)
        # dynamic-topology clocks/accounting (stay zero on static topologies)
        self._dyn_seq = 0            # per-transfer jitter draw counter
        self.stall_seconds = 0.0     # time lost vs nominal static transfer cost
        self.n_retries = 0           # outage-interrupted collective restarts
        # fair-share traffic plane: in-flight flows share link capacity via
        # max-min water-filling instead of queueing on channels
        self._fairshare: "FairShareSim | None" = None
        if ccfg.channel_scheduler == "fairshare":
            self._fairshare = FairShareSim(self.topology,
                                           reform_fn=self._fs_reform,
                                           finish_fn=self._fs_finish)
        # per-transfer sojourn (initiation -> finish wall seconds, queueing
        # INCLUDED) keyed by transfer id; fair-share entries hold the current
        # projection until the flow finalizes
        self._transfer_log: Dict[int, float] = {}
        self.multipath_splits = 0    # transfers whose plan split a payload
        # Eq. 9 latency/bandwidth decomposition anchors
        self._ref_wire_bytes = self._wire_bytes(int(mean_frag_bytes))
        self._lat_startup = self.topology.allreduce_time(0)

    # ------------------------------------------------------------ properties

    def _materialize(self, flat_buf):
        """Flat-plane buffer -> single-model pytree (fused_updates only).
        unpack_full writes every fragment's rows, so a zeros template of the
        right shapes/dtypes is sufficient."""
        tmpl = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            self._model_sds)
        return self.frag.flat.unpack_full(tmpl, flat_buf)

    @property
    def theta_g(self):
        """Consensus model as a pytree. With `fused_updates` the state holds
        a flat plane; reads materialize a pytree copy (eval/checkpoint-rate
        boundary, not the transition hot path)."""
        if self.cfg.fused_updates:
            return self._materialize(self.state.theta_g)
        return self.state.theta_g

    @theta_g.setter
    def theta_g(self, value):
        if self.cfg.fused_updates:
            value = self.frag.flat.pack_full(value)
        self.state = dataclasses.replace(self.state, theta_g=value)

    @property
    def momentum(self):
        if self.cfg.fused_updates:
            return self._materialize(self.state.momentum)
        return self.state.momentum

    @momentum.setter
    def momentum(self, value):
        if self.cfg.fused_updates:
            value = self.frag.flat.pack_full(value)
        self.state = dataclasses.replace(self.state, momentum=value)

    @property
    def in_flight(self) -> List[PendingSync]:
        """Back-compat view of the in-flight schedule (initiation order)."""
        return list(self.pending)

    @property
    def adaptive(self) -> adaptive_lib.AdaptiveState:
        """Host snapshot of the Eq. 11 scheduler state. This BLOCKS until the
        device has computed the rates and last-sync steps (host sync
        `adaptive_rate`)."""
        rate, last = self.telemetry.read(
            "adaptive_rate", (self.state.rate, self.state.last_sync))
        return adaptive_lib.AdaptiveState(
            K=self.K, H=self.H,
            last_sync=[int(x) for x in last],
            rate=[float(r) for r in rate])

    # ------------------------------------------------------------------ utils

    def set_worker_availability(self, worker: int, available: bool):
        """Mark a datacenter online/offline (WAN partition / maintenance).
        Offline workers are excluded from subsequent syncs until restored."""
        self.worker_available[worker] = available
        self.state = dataclasses.replace(
            self.state,
            worker_available=self.state.worker_available.at[worker].set(
                bool(available)))

    def _sparsify(self, d):
        """Top-k magnitude sparsification per leaf (sync_topk_frac < 1)."""
        return es.sparsify(d, self.cfg.sync_topk_frac)

    def _wire_bytes(self, nbytes: int) -> int:
        """Bytes that actually cross the WAN for an `nbytes` f32 fragment:
        wire-codec quantization (codes + per-block scales), sync_dtype
        compression and top-k sparsification (values + indices). ONE
        accounting rule for blocking and overlapped paths alike."""
        if self.cfg.wire_codec != "none":
            # quantized wire format: `bits`-bit codes + one f32 scale per
            # codec_block elements (kernels/delta_codec). Subsumes sync_dtype
            # — the codec quantizes whatever dtype the payload was in.
            from repro.kernels.delta_codec import ops as codec_ops
            nbytes = codec_ops.wire_bytes(nbytes // 4,
                                          codec=self.cfg.wire_codec,
                                          block=self.cfg.codec_block)
        elif jnp.dtype(self.cfg.sync_dtype).itemsize < 4:
            nbytes = nbytes * jnp.dtype(self.cfg.sync_dtype).itemsize // 4
        if self.cfg.sync_topk_frac < 1.0:
            # sparse wire format: values + indices
            nbytes = int(nbytes * min(1.0, 2 * self.cfg.sync_topk_frac))
        return int(nbytes)

    # ------------------------------------------------------- routed planning

    def _active_plan(self, t: float) -> CommPlan:
        """The routed plan valid at wall-time t, re-planning when t falls
        outside the cached plan's validity window (either a
        `LinkDynamics.next_change` edge passed, or t precedes a plan a queued
        future transfer fetched — the window check is two-sided so a
        wall-clock query never sees a future plan's state). Applies plan side
        effects (Algorithm-2 cost vector, dark-region availability) on route
        change; counting happens in `_note_plan_use` at transfer use only."""
        if self._plan is not None and \
                self._plan.valid_from <= t < self._plan.valid_until:
            return self._plan
        plan = self._planner.plan_at(t)
        if self._plan is None or plan.route_key() != self._plan.route_key():
            self._apply_plan(plan)
        self._plan = plan
        self._plan_time = float(t)
        return plan

    def _note_plan_use(self, plan: CommPlan, t: float):
        """Count reroutes/hub elections against the last TRANSFER-used plan.
        (Sampling at wall-clock refreshes instead would make the counters
        depend on the host loop's cadence — per-step vs segment-scanned.)"""
        key = plan.route_key()
        if self._counted_key is not None and key != self._counted_key:
            self.reroutes += 1
            if plan.hub != self._counted_hub:
                self.hub_elections += 1
        self._counted_key = key
        self._counted_hub = plan.hub
        self._counted_time = float(t)

    def _transfer_plan_fn(self, t: float) -> CommPlan:
        """Plan fetch for transfer scheduling/integration: the active plan at
        t, with the use counted."""
        plan = self._active_plan(t)
        self._note_plan_use(plan, t)
        return plan

    def _plan_frag_cost(self, plan: CommPlan) -> List[float]:
        return [self.topology.plan_allreduce_time(
                    plan, self._wire_bytes(self.frag.fragment_bytes(p)))
                for p in range(self.K)]

    def _apply_plan(self, plan: CommPlan):
        """Plan side effects: refresh the Algorithm-2 cost vector from the
        active routes, and toggle availability for regions the plan dropped
        as dark. The availability each region had when it went dark (user
        knob included) is recorded and restored VERBATIM on recovery, so the
        planner never silently re-enables a user-disabled worker."""
        self._frag_cost = self._plan_frag_cost(plan)
        dark = set(range(self.M)) - set(plan.participants)
        for r in sorted(dark - set(self._plan_dark)):
            self._plan_dark[r] = bool(self.worker_available[r])
            if self.worker_available[r]:
                self.set_worker_availability(r, False)
        for r in sorted(set(self._plan_dark) - dark):
            if self._plan_dark.pop(r):
                self.set_worker_availability(r, True)

    def _schedule_transfer(self, nbytes: int) -> Tuple[float, float]:
        """Queue one collective of `nbytes` (raw f32) on the WAN: applies the
        wire format, grabs the earliest-free channel, accounts per-link
        traffic. Returns ``(finish_wall_time, measured_duration)`` (duration
        excludes queueing — it is the Eq. 9 re-derivation's T_s sample).

        Static topologies keep the original closed-form arithmetic bitwise;
        with `Topology.dynamics` the finish time integrates the time-varying
        bottleneck bandwidth (and the engine-owned `_dyn_seq` counter makes
        per-transfer jitter a pure function of serialized state). With
        routing enabled the collective executes over the ACTIVE CommPlan's
        multi-hop routes and participants instead of the fixed formulas.

        With `channel_scheduler="fairshare"` there is no channel queue at all:
        the transfer joins the fair-share flow set immediately and its finish
        time is the max-min water-filling projection over everyone sharing
        its links (re-projected whenever a later transfer arrives)."""
        wire = self._wire_bytes(nbytes)
        tid = self.n_syncs              # unique, monotonic transfer id
        if self._fairshare is not None:
            finish, duration = self._fairshare_schedule(tid, wire)
            self.bytes_sent += wire
            self.wire_bytes_raw += int(nbytes)
            self.n_syncs += 1
            return finish, duration
        ch = min(range(len(self._channel_free)),
                 key=lambda i: self._channel_free[i])
        start = max(self.wall_clock, self._channel_free[ch])
        dyn = self.topology.dynamics
        if self._planner is not None:
            jitter = 1.0
            if dyn is not None:
                jitter = dyn.jitter_mult(self._dyn_seq)
                self._dyn_seq += 1
            # re-plannable integration: if the routes go dark mid-transfer
            # the collective re-forms on the fresh plan (fetched through
            # `_transfer_plan_fn`, so reroute/election counters track it)
            finish, nominal, retries, segments = \
                self.topology.routed_transfer_time(
                    self._transfer_plan_fn, wire, start, jitter=jitter)
            # `(start + nominal) - start` loses an ulp vs nominal; on a static
            # topology the routed accounting must equal the fixed-route path's
            actual = (finish - start) if dyn is not None else nominal
            self.n_retries += retries
            self.stall_seconds += max(0.0, actual - nominal)
            self.comm_seconds += actual
            scale = (actual / nominal if nominal > 0 else 1.0)
            # per-link traffic split across the plans that actually carried
            # the payload (a re-formed transfer charges the stand-in routes
            # for their share, not the abandoned dark ones)
            for seg_plan, frac in segments:
                if frac <= 0.0:
                    continue
                self.link_seconds += self.topology.plan_link_seconds(
                    seg_plan, wire) * (scale * frac)
                self.link_bytes += self.topology.plan_link_bytes(
                    seg_plan, wire) * frac
            if any(seg_plan.is_split for seg_plan, _ in segments):
                self.multipath_splits += 1
        elif dyn is None:
            t_s = self.topology.t_s(wire)
            finish = start + t_s
            self.comm_seconds += t_s
            self.link_seconds += self.topology.link_seconds(wire)
            self.link_bytes += self.topology.link_bytes(wire)
        else:
            jitter = dyn.jitter_mult(self._dyn_seq)
            self._dyn_seq += 1
            finish, nominal, retries = self.topology.transfer_time(
                wire, start, jitter=jitter)
            self.n_retries += retries
            self.stall_seconds += max(0.0, (finish - start) - nominal)
            self.comm_seconds += finish - start   # actual WAN occupancy
            # per-link busy-seconds scale with the ACTUAL duration (stall
            # attributed proportionally across the collective's links), so
            # link accounting reconciles with comm_seconds
            scale = (finish - start) / nominal if nominal > 0 else 1.0
            self.link_seconds += self.topology.link_seconds(wire) * scale
            self.link_bytes += self.topology.link_bytes(wire)
        self._channel_free[ch] = finish
        self.bytes_sent += wire
        self.wire_bytes_raw += int(nbytes)
        self.n_syncs += 1
        # sojourn = initiation -> finish, queueing INCLUDED (unlike duration)
        self._transfer_log[tid] = finish - self.wall_clock
        return finish, finish - start

    # ------------------------------------------------- fair-share scheduling

    def _fairshare_schedule(self, tid: int, wire: int) -> Tuple[float, float]:
        """Admit one collective into the fair-share flow set at the current
        wall-clock and re-project every in-flight transfer's finish time
        (arrivals only ever slow others down, so deliveries already made stay
        consistent). Returns ``(projected_finish, projected_duration)``."""
        sim = self._fairshare
        request = self.wall_clock
        sim.advance(request)
        spec = self._fs_flow_spec(request, wire, effectful=True)
        dyn = self.topology.dynamics
        jitter = 1.0
        if dyn is not None:
            jitter = dyn.jitter_mult(self._dyn_seq)
            self._dyn_seq += 1
        if spec["multipath"]:
            self.multipath_splits += 1
        sim.add_flow(tid, spec, request, wire, jitter)
        finishes = sim.project()
        by_tid = {ev.tid: ev for ev in self.pending}
        for fid, (fstart, ffinish) in finishes.items():
            self._transfer_log[fid] = ffinish - fstart
            ev = by_tid.get(fid)
            if ev is not None:
                ev.finish_time = ffinish
                ev.duration = ffinish - fstart
                ev.deliver_at = self._deliver_step_for(ev.t_init, ffinish)
        _, finish = finishes[tid]
        return finish, finish - request

    def _fs_flow_spec(self, t: float, wire: int, effectful: bool) -> Dict:
        """Fair-share flow description of one collective at wall-time t: link
        weights (busy-seconds per unit progress; bottleneck = 1), latency
        phases, unit-rate bandwidth work, and the accounting matrices. Routed
        engines derive it from the plan at t (`effectful=False` uses the pure
        `plan_at` so projections leak no planner side effects)."""
        topo = self.topology
        if self._planner is not None:
            plan = (self._transfer_plan_fn(t) if effectful
                    else self._planner.plan_at(t))
            return self._fs_pack_spec(
                topo.plan_link_bw_seconds(plan, wire),
                topo.plan_allreduce_time(plan, 0),
                topo.plan_n_latency_phases(plan),
                topo.plan_allreduce_time(plan, wire),
                topo.plan_link_seconds(plan, wire),
                topo.plan_link_bytes(plan, wire),
                multipath=plan.is_split)
        return self._fs_pack_spec(
            topo.link_bw_seconds(wire), topo.allreduce_time(0),
            topo.n_latency_phases, topo.allreduce_time(wire),
            topo.link_seconds(wire), topo.link_bytes(wire))

    @staticmethod
    def _fs_pack_spec(bsec, lat, phases, nominal, sec, link_bytes,
                      multipath: bool = False) -> Dict:
        work = float(bsec.max(initial=0.0))
        links = {}
        if work > 0.0:
            for i, j in np.argwhere(bsec > 0.0):
                links[(int(i), int(j))] = float(bsec[i, j] / work)
        return {"links": links, "lat": float(lat), "phases": int(phases),
                "work": work, "nominal": float(nominal), "sec": sec,
                "bytes": link_bytes, "multipath": bool(multipath)}

    def _fs_reform(self, t: float, wire: int, effectful: bool):
        """Mid-transfer re-plan hook for the fair-share sim (None on static
        routing: the flow waits out the outage on its links, like serial)."""
        if self._planner is None:
            return None
        return self._fs_flow_spec(t, wire, effectful)

    def _fs_finish(self, flow, finish: float):
        """Finalize one fair-share flow's accounting (the serial path's
        schedule-time accounting, deferred to actual completion): WAN
        occupancy, stall vs nominal, retries, and the per-link traffic split
        across the plans that carried the payload."""
        actual = finish - flow.start
        self.comm_seconds += actual
        self.stall_seconds += max(0.0, actual - flow.nominal)
        self.n_retries += flow.retries
        scale = actual / flow.nominal if flow.nominal > 0 else 1.0
        self.link_seconds += (flow.acc_sec
                              + flow.cur_sec * flow.frac_in) * scale
        self.link_bytes += flow.acc_bytes + flow.cur_bytes * flow.frac_in
        self._transfer_log[flow.id] = actual

    def _deliver_step_for(self, t: int, finish_time: float) -> int:
        """First step whose end-of-step wall-clock covers `finish_time`
        (overlapped methods never block, so wall(t') = (t'+1) * T_c)."""
        t_c = self.topology.t_c
        if t_c <= 0:
            return t + 1
        return max(t + 1, math.ceil(finish_time / t_c - 1e-9) - 1)

    # ------------------------------------------------------------ initiation

    def _initiate(self, t: int, params_stack, p: int):
        with span("protocol.initiate", frag=p):
            nbytes = self.frag.fragment_bytes(p)
            tid = self.n_syncs          # _schedule_transfer's id, pre-bump
            finish, duration = self._schedule_transfer(nbytes)
            self.state = self._fns.initiate(self.state, t, params_stack, p)
            self.pending.append(PendingSync(
                frag=p, t_init=t, deliver_at=self._deliver_step_for(t, finish),
                finish_time=finish, seq=self._seq, duration=duration,
                wire=self._wire_bytes(nbytes), tid=tid))
            self._seq += 1
            self.telemetry.initiations += 1

    def _select_cocodc(self, t: int, busy: set) -> int:
        """Algorithm 2's fragment; blocks on `adaptive`'s device read."""
        # _frag_cost tracks the wall-clock plan (refreshed in on_step_end
        # before deliveries/initiations), so pricing sees the CURRENT routes
        with span("protocol.select"):
            costs = self._frag_cost if self.cfg.link_pricing else None
            return adaptive_lib.select_fragment(self.adaptive, t, busy,
                                                costs=costs)

    # ------------------------------------------------------ event-driven API

    def next_event_step(self, t: int) -> "int | None":
        """Smallest step t' >= t at which `on_step_end(t', ...)` performs a
        protocol action: a scheduled initiation slot, a due delivery, or a
        blocking round. None when the method schedules no events (e.g.
        method="local" — the host loop may fuse every remaining step into one
        scanned segment).

        The schedule of WHEN is the registered `SyncMethod` strategy's call;
        WHICH fragment a cocodc initiation picks is data-dependent (Eq. 11),
        so the caller must re-query after every event."""
        return self.method_impl.next_event_step(self, t)

    def advance_steps(self, n: int):
        """Account wall-clock for `n` quiet local steps (no protocol event) —
        the steps a scanned segment fused away. Accumulated per-step to stay
        bitwise-identical with the per-step loop's repeated `+= t_c`."""
        for _ in range(n):
            self.wall_clock += self.topology.t_c

    # ------------------------------------------------------------- main hook

    def on_step_end(self, t: int, params_stack):
        """Call after inner step t (0-based). Ticks the wall-clock, then
        dispatches the method strategy's protocol action (blocking round,
        delivery processing + initiation, or nothing). Returns the updated
        params_stack."""
        with span("protocol", t=t):
            self.wall_clock += self.topology.t_c
            out = self.method_impl.on_step_end(self, t, params_stack)
            if self._fairshare is not None:
                # advance the fluid sim to the post-step wall-clock,
                # finalizing flows that finished (advance is associative, so
                # per-step and segment-fused loops land on identical states)
                self._fairshare.advance(self.wall_clock)
            return out

    def _process_deliveries(self, t: int, params_stack):
        """Apply every in-flight delivery due at step t (delivery order:
        deliver_at, then initiation seq) and feed measured durations to the
        Eq. 9 re-derivation window. Shared by all overlapped strategies."""
        due = sorted((ev for ev in self.pending if ev.deliver_at <= t),
                     key=lambda e: (e.deliver_at, e.seq))
        for ev in due:
            with span("protocol.deliver", frag=ev.frag, t_init=ev.t_init):
                self.state, params_stack = self._fns.deliver(
                    self.state, t, params_stack, ev.frag)
                self.pending.remove(ev)
                if self._resync is not None:
                    # a COMPLETED transfer's measured duration is shared
                    # history (paired with its wire bytes for the Eq. 9 fit)
                    self._resync.observe(ev.duration, ev.wire)
                self.telemetry.deliveries += 1
        return params_stack

    # ---------------------------------------------------------- checkpointing

    def scheduler_state(self) -> Dict[str, object]:
        """Host-side scheduler state (everything outside the EngineState
        pytree) as plain serializable containers — the in-flight schedule,
        WAN-channel clocks, and traffic accounting. The simulated wall-clock
        itself lives in TrainerState (single authority), not here."""
        return {
            "schema_version": SCHEDULER_SCHEMA_VERSION,
            "pending": [[ev.frag, ev.t_init, ev.deliver_at, ev.finish_time,
                         ev.seq, ev.duration, ev.wire, ev.tid]
                        for ev in self.pending],
            "seq": self._seq,
            "comm_seconds": self.comm_seconds,
            "bytes_sent": self.bytes_sent,
            "wire_bytes_raw": self.wire_bytes_raw,
            "n_syncs": self.n_syncs,
            "channel_free": [float(x) for x in self._channel_free],
            "worker_available": [bool(x) for x in self.worker_available],
            "link_bytes": self.link_bytes,
            "link_seconds": self.link_seconds,
            # dynamics clocks: the jitter draw counter + stall/retry tallies
            # (exact mid-transfer resume on time-varying links needs these)
            "dyn_seq": self._dyn_seq,
            "stall_seconds": self.stall_seconds,
            "n_retries": self.n_retries,
            # routed-planner state: the active plan is a pure function of its
            # plan time, so serializing the TIME (plus counters and the
            # planner-dropped regions) replays mid-outage resume bitwise
            "routing": {
                "plan_time": (-1.0 if self._plan_time is None
                              else float(self._plan_time)),
                "counted_time": (-1.0 if self._counted_time is None
                                 else float(self._counted_time)),
                "plan_dark": [[int(r), bool(prior)] for r, prior
                              in sorted(self._plan_dark.items())],
                "reroutes": int(self.reroutes),
                "hub_elections": int(self.hub_elections),
            },
            # Eq. 9/10 re-derivation window + the currently derived cadence
            "resync": {
                "measured": ([] if self._resync is None
                             else [float(x) for x in self._resync.measured]),
                "measured_bytes": ([] if self._resync is None else
                                   [float(x)
                                    for x in self._resync.measured_bytes]),
                "N": int(self.N),
                "h_cocodc": int(self.h_cocodc),
            },
            # fair-share traffic plane: the in-flight flow set (None under the
            # serial scheduler) + the per-transfer sojourn log
            "multipath_splits": int(self.multipath_splits),
            "transfer_log": [[int(k), float(v)] for k, v
                             in sorted(self._transfer_log.items())],
            "fairshare": (None if self._fairshare is None
                          else self._fairshare.state_dict()),
        }

    def restore_scheduler(self, st: Dict[str, object]):
        """Inverse of `scheduler_state` (EngineState is restored separately).
        Accepts any prior schema version — `upgrade_scheduler_state` is the
        single upgrade path; no per-field fallbacks live here."""
        st = upgrade_scheduler_state(st)
        self.pending = [PendingSync(frag=int(r[0]), t_init=int(r[1]),
                                    deliver_at=int(r[2]),
                                    finish_time=float(r[3]), seq=int(r[4]),
                                    duration=float(r[5]), wire=int(r[6]),
                                    tid=int(r[7]))
                        for r in st["pending"]]
        self._seq = int(st["seq"])
        self.comm_seconds = float(st["comm_seconds"])
        self.bytes_sent = int(st["bytes_sent"])
        self.wire_bytes_raw = int(st["wire_bytes_raw"])
        self.n_syncs = int(st["n_syncs"])
        self._channel_free = [float(x) for x in st["channel_free"]]
        self.worker_available = [bool(x) for x in st["worker_available"]]
        self.link_bytes = np.asarray(st["link_bytes"], dtype=np.float64)
        self.link_seconds = np.asarray(st["link_seconds"], dtype=np.float64)
        self._dyn_seq = int(st["dyn_seq"])
        self.stall_seconds = float(st["stall_seconds"])
        self.n_retries = int(st["n_retries"])
        routing = st["routing"]
        self.reroutes = int(routing["reroutes"])
        self.hub_elections = int(routing["hub_elections"])
        self._plan_dark = {int(row[0]): bool(row[1])
                           for row in routing["plan_dark"]}
        plan_time = float(routing["plan_time"])
        self._plan = None
        self._plan_time = None
        self._counted_time = None
        self._counted_key = None
        self._counted_hub = None
        if self._planner is not None:
            if plan_time >= 0.0:
                # re-derive the active plan from its serialized plan time
                # (pure function) and refresh the cost vector from it;
                # availability was restored above/inside EngineState, so no
                # side effects re-run
                self._plan_time = plan_time
                self._plan = self._planner.plan_at(plan_time)
                self._frag_cost = self._plan_frag_cost(self._plan)
            counted_time = float(routing["counted_time"])
            if counted_time >= 0.0:
                counted = self._planner.plan_at(counted_time)
                self._counted_time = counted_time
                self._counted_key = counted.route_key()
                self._counted_hub = counted.hub
        resync = st["resync"]
        if self._resync is not None:
            self._resync.measured = [float(x) for x in resync["measured"]]
            self._resync.measured_bytes = [float(x) for x
                                           in resync["measured_bytes"]]
        if resync["N"] is not None:
            self.N = int(resync["N"])
        if resync["h_cocodc"] is not None:
            self.h_cocodc = int(resync["h_cocodc"])
        self.multipath_splits = int(st["multipath_splits"])
        self._transfer_log = {int(k): float(v) for k, v in st["transfer_log"]}
        if self._fairshare is not None and st["fairshare"] is not None:
            self._fairshare.load_state(st["fairshare"])

    # ---------------------------------------------------------------- stats

    def stats(self) -> Dict[str, float]:
        sojourns = sorted(self._transfer_log.values())
        return {
            "wall_clock_s": float(self.wall_clock),
            "comm_seconds": float(self.comm_seconds),
            "bytes_sent": float(self.bytes_sent),
            "wire_bytes_total": float(self.bytes_sent),
            "wire_bytes_raw": float(self.wire_bytes_raw),
            "compression_ratio": float(
                1.0 if self.bytes_sent == 0
                else self.wire_bytes_raw / self.bytes_sent),
            "n_syncs": float(self.n_syncs),
            "mean_transfer_s": float(
                0.0 if self.n_syncs == 0
                else self.comm_seconds / self.n_syncs),
            "overlap_ratio": float(0.0 if self.wall_clock == 0 else
                                   min(1.0, self.comm_seconds / self.wall_clock)),
            "target_syncs_N": float(self.N),
            "busiest_link_bytes": float(self.link_bytes.max(initial=0.0)),
            "busiest_link_seconds": float(self.link_seconds.max(initial=0.0)),
            "stall_seconds": float(self.stall_seconds),
            "stall_fraction": float(0.0 if self.comm_seconds == 0 else
                                    self.stall_seconds / self.comm_seconds),
            "n_retries": float(self.n_retries),
            "reroutes": float(self.reroutes),
            "hub_elections": float(self.hub_elections),
            # per-transfer sojourn (initiation -> finish, queueing INCLUDED —
            # the scheduler-comparison metric; `mean_transfer_s` above keeps
            # its queueing-excluded occupancy semantics)
            "transfer_mean_s": float(np.mean(sojourns)) if sojourns else 0.0,
            "transfer_p50_s": _percentile(sojourns, 0.50),
            "transfer_p95_s": _percentile(sojourns, 0.95),
            "multipath_splits": float(self.multipath_splits),
            "max_link_busy_fraction": float(
                0.0 if self.wall_clock <= 0
                else self.link_seconds.max(initial=0.0) / self.wall_clock),
        }

    def link_stats(self) -> Dict[str, object]:
        """Per-link transfer accounting over the run (region-name keyed)."""
        regions = self.topology.regions
        links = {}
        m = self.M
        wall = float(self.wall_clock)
        for i in range(m):
            for j in range(m):
                if self.link_bytes[i, j] > 0:
                    # busy-seconds accrue PER FLOW (occupancy scaled by
                    # actual/nominal duration), so under fairshare a link
                    # shared by concurrent flows can exceed 1.0 — read it
                    # as demand on the link, like a load average
                    links[f"{regions[i]}->{regions[j]}"] = {
                        "bytes": float(self.link_bytes[i, j]),
                        "busy_seconds": float(self.link_seconds[i, j]),
                        "busy_fraction": float(
                            0.0 if wall <= 0
                            else self.link_seconds[i, j] / wall),
                    }
        busiest = None
        if links:
            busiest = max(links, key=lambda k: links[k]["busy_seconds"])
        return {"links": links, "busiest_link": busiest,
                "collective": self.topology.collective,
                "routing": self.cfg.routing,
                "hub": int(self._plan.hub if self._plan is not None
                           else self.topology.hub),
                "regions": list(regions)}
