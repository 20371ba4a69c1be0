"""Heartbeat executor (paper §3.2, §4.2, Algorithm 1) — pipelined.

While one batch of queries and updates executes, newly arriving work queues;
at each heartbeat the queues are drained (up to the per-template slot
capacity — excess stays queued for the next cycle, exactly the paper's
admission rule) and pushed through ONE jitted global-plan step.

The heartbeat is split into two phases so host and device overlap:

  dispatch() — drain the queues into PREALLOCATED staging buffers, stage
               the batch onto the device, and launch the cycle.  JAX
               dispatch is asynchronous, so this returns while the device
               still computes.
  collect()  — block on the oldest in-flight cycle and route its results
               to the waiting tickets.

With double-buffered admission (two staging buffer sets, pipeline depth
2), the queue draining and numpy parameter staging for heartbeat N+1
overlap with device execution of heartbeat N.  A query admitted at
dispatch k completes at collect k, so the paper's latency accounting is
unchanged: a query waits at most one cycle in the queue plus one cycle of
processing => worst-case latency = 2 x cycle time (§3.5).

``run_cycle()`` (dispatch immediately followed by collect) preserves the
original synchronous semantics for callers that want them.

Scans AND joins are incremental: every heartbeat returns a functional
carry — the shared scans' bitmask words plus the partitioned joins' key
partitions — and exposes each join's matched-row-id arrays in
``results["_join_rids"]``, which the executor threads forward as the
rid half of the widened carry.  The next dispatch — when the carried
state exists and the heartbeat's deltas fit their fixed capacities
(changed admission slots per stage pane, update-touched rows per table
dirty set) — runs a DELTA cycle, which re-evaluates only those deltas
against the carried words (lowering.build_delta_cycle); when
additionally NO carried join's PK table was touched (its partitions
would rebuild, invalidating carried rids), the delta cycle's
``delta_joins`` variant also re-probes only the dirty spine rows
against the carried rid arrays.  All choices are made host-side from
exact admission knowledge, so ineligible heartbeats fall back — full
rescan for the scans (which reseeds BOTH carry halves), full probe for
the joins — without any data-dependent branching on device.  The
scan/parts carry is donated: it is produced by one heartbeat and
consumed by exactly the next, so pipelined in-flight cycles never alias
it.  The rid carry is NOT donated — its arrays are also the in-flight
``results["_join_rids"]`` a later collect still reads — and the
host-side ``changed`` staging vector is double-buffered with the rest
of the admission buffers for the same reason.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import os
import threading
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import folding
from repro.core.backends import counting_backend, resolve_backend
from repro.core.lowering import (PARTITIONED_MIN_CAPACITY, build_cycle,
                                 build_delta_cycle, lower_plan, stage_map)
from repro.core.plan import CompiledPlan, QueryTemplate
from repro.core.storage import (UPDATE_BATCH_RESET, UpdateSlots,
                                empty_update_batch)


def check_carry_layout(carry_token, layout_token) -> None:
    """Always-on carry/layout guard (deliberately NOT an assert).

    A delta heartbeat must never consume a carry produced under a
    different admission layout — the carried words/rids are positional
    in it — and under ``python -O`` an assert would vanish, letting the
    mismatch corrupt results silently.  Both the delta dispatch path and
    the fold carry-migration path route through this one check.
    """
    if carry_token != layout_token:
        raise RuntimeError(
            "delta heartbeat would consume a carry produced under a "
            "different admission layout — reset the carries "
            f"(carry {carry_token} != plan {layout_token})")


def _measure_key_stats(plan: CompiledPlan,
                       initial_data) -> Dict[str, Dict[str, int]]:
    """Measured key skew of every partitioned-join candidate PK table
    (index-less, at or above the partitioned threshold), from the
    initial snapshot: live-row count and widest duplicate-key run.
    ``lower_plan`` feeds it to the adaptive ``partition_layout`` so the
    probe pane width matches real occupancy."""
    stats = {}
    for t, schema in plan.catalog.schemas.items():
        if (schema.pk is None or schema.key_space > 0
                or schema.capacity < PARTITIONED_MIN_CAPACITY):
            continue
        data = (initial_data or {}).get(t, {})
        keys = np.asarray(data.get(schema.pk, ()))
        if keys.size:
            _, counts = np.unique(keys, return_counts=True)
            stats[t] = {"n_live": int(keys.size),
                        "max_dup": int(counts.max())}
        else:
            stats[t] = {"n_live": 0, "max_dup": 1}
    return stats


def _clear_counts_at_entry(fn, counts: Dict[str, int], name: str):
    """Reset a flavour's backend-op counter when its cycle (re)traces.

    Backend ops fire at TRACE time under jit, so the counts are the
    per-beat STATIC launch counts of the traced cycle; clearing at
    traced-function entry makes retraces overwrite rather than
    accumulate.  With ``jit=False`` every call re-enters, so the counts
    are per-call either way.  ``name`` names the jitted module
    (``jit_<name>``), so a device trace says which flavour ran."""
    def wrapped(*args):
        counts.clear()
        return fn(*args)
    wrapped.__name__ = wrapped.__qualname__ = name
    return wrapped


def _arg_spec(x) -> jax.ShapeDtypeStruct:
    """The abstract value a jitted call saw for one argument leaf (an
    uncommitted array lowers without a sharding)."""
    return jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=x.sharding if x.committed else None)


@dataclasses.dataclass
class Ticket:
    id: int
    template: str
    params: Any
    submit_time: float
    done_time: Optional[float] = None
    result: Any = None

    @property
    def latency(self) -> float:
        return (self.done_time - self.submit_time) if self.done_time else None


class _BeatSpans:
    """One beat's host phases, each recorded as ``(name, start, end)`` on
    ``time.perf_counter`` and tagged with the beat's sequence number
    ``seq``.  Every span is also a ``jax.profiler.TraceAnnotation``
    (argument ``beat=seq``), so a running profiler trace holds it on its
    own clock; with no trace running it costs about a microsecond.

    The spans of a beat: ``beat.admit`` (with ``beat.fill``, the queue
    drain and staging-buffer fill, and ``beat.h2d``, every host-to-device
    copy, inside it), ``beat.launch``, ``beat.wait`` (the collect-side
    ``block_until_ready``) and ``beat.route`` (result assembly,
    device-to-host copies, ticket routing)."""

    __slots__ = ("seq", "spans")

    def __init__(self, seq: int):
        self.seq = seq
        self.spans: List[tuple] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        """Time the block as span ``name``; yields its start."""
        start = time.perf_counter()
        try:
            with TraceAnnotation(name, beat=self.seq):
                yield start
        finally:
            self.spans.append((name, start, time.perf_counter()))

    def seconds(self, name: str) -> float:
        return sum(e - s for n, s, e in self.spans if n == name)


class _StagingBuffers:
    """Preallocated host-side admission buffers for ONE pipeline slot.

    Rebuilding every numpy array per heartbeat put allocation on the
    critical path; these persist for the engine's lifetime and only the
    activation/mask fields are cleared between uses (parameter/payload
    slots are masked out by ``active``/``*_mask`` and may hold stale
    values).

    Query admission is PACKED: one contiguous [qcap, P_max, 2] parameter
    buffer plus one [qcap] active vector cover every template (each
    template owns the rows of its static slot range), so staging a
    heartbeat is a single host->device copy per buffer instead of
    O(templates) transfers.
    """

    def __init__(self, plan: CompiledPlan, slots: UpdateSlots):
        self.params = np.zeros((plan.qcap, plan.n_params_max, 2), np.int32)
        self.active = np.zeros((plan.qcap,), bool)
        # per-slot staging for the delta path's changed-slot vector: like
        # params/active it is staged with a zero-copy-capable asarray, so
        # it must be double-buffered with the rest — an in-flight delta
        # cycle must never alias a later dispatch's overwrite
        self.changed = np.zeros((plan.qcap,), bool)
        # same layout as the device batches, numpy-backed (ONE source of
        # truth: storage.empty_update_batch)
        self.updates: Dict[str, Dict[str, Any]] = {
            t: empty_update_batch(schema, slots, xp=np)
            for t, schema in plan.catalog.schemas.items()}

    def reset(self) -> None:
        self.active[:] = False
        for b in self.updates.values():
            for field, fill in UPDATE_BATCH_RESET.items():
                b[field][:] = fill


@dataclasses.dataclass
class CycleResult:
    """One collected heartbeat: routed tickets + its observed wall time.

    ``wall_s`` is the collector-side inter-completion time (elapsed from
    the previous collect's return — or the drain start — to this one),
    which under pipelining is the achieved cycle time the paper's
    2 x cycle-time latency bound is stated against (§3.5).

    ``admitted``/``dirty`` count the queries and update-touched rows the
    heartbeat carried and ``scan_path``/``join_path`` name the scan and
    join flavours it ran ("delta" or "full"; "mixed" when backpressure
    folded several heartbeats into one collect; ``join_path`` is ""
    when the plan has no delta-eligible join stages) — the attribution
    benchmarks and the SLA gate need to split cycle time between the
    paths.

    The ``t_*_s`` fields are the beat's per-phase host-time breakdown,
    the lengths of its spans (``_BeatSpans``) — staging
    (``beat.admit``: queue drain + buffer fill + H2D), dispatch
    (``beat.launch``, the async cycle launch), kernel (``beat.wait``,
    the collect-side block_until_ready wait) and collect
    (``beat.route``: result assemble + ticket routing) — and
    ``backend_ops`` its per-op backend launch counts (from the traced
    cycle), so the fused path's one-launch claim is machine-checkable
    per beat."""
    tickets: Dict[str, List[Ticket]]
    wall_s: float
    admitted: int = 0
    dirty: int = 0
    scan_path: str = ""
    join_path: str = ""
    t_stage_s: float = 0.0
    t_dispatch_s: float = 0.0
    t_kernel_s: float = 0.0
    t_collect_s: float = 0.0
    backend_ops: Dict[str, int] = dataclasses.field(default_factory=dict)


#: The shipped donation contract: cycle flavour -> donate_argnums.  The
#: snapshot (arg 0) rolls forward functionally in place; the delta
#: flavours additionally donate the carried scan words + key partitions
#: (arg 1).  The rid carry (arg 2 of the delta-join flavour) is
#: deliberately NOT donated — its arrays double as the previous beat's
#: in-flight ``results["_join_rids"]``.  planlint's use-after-donate
#: pass and the lint CLI verify this spec against the aliasing the
#: lowering actually emits.
DONATION_SPEC: Dict[str, tuple] = {
    "full": (0,), "delta": (0, 1), "delta_join": (0, 1)}


@dataclasses.dataclass
class _CompiledHandle:
    """One fully-built compiled-cycle generation.

    The executor is double-buffered across a FOLD (core/folding.py): it
    keeps serving from the installed handle while a background thread
    builds the next one for the extended plan; the swap installs the new
    handle atomically at a beat boundary.  Everything layout-dependent
    lives here, so installing a handle IS the layout swap."""
    plan: CompiledPlan
    lowered: Any
    backend_ops: Dict[str, Dict[str, int]]
    cycle: Any
    cycle_delta: Any
    cycle_delta_join: Any
    shard_spec: Any
    device_merge: Any
    assemble: Any
    stage: Any
    carried_joins: tuple
    layout_token: tuple
    #: the shipped donation contract per cycle flavour (flavour ->
    #: donate_argnums), recorded so planlint's use-after-donate pass and
    #: the lint CLI verify the REAL spec instead of a hardcoded copy;
    #: empty when the engine runs unjitted
    donation: Dict[str, tuple] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class _PendingFold:
    """A fold in flight: the extended plan + its background build."""
    plan: CompiledPlan
    handle: Optional[_CompiledHandle] = None
    error: Optional[BaseException] = None
    thread: Optional[threading.Thread] = None
    built: threading.Event = dataclasses.field(
        default_factory=threading.Event)

    def ready(self) -> bool:
        return self.built.is_set()


@dataclasses.dataclass
class _InFlight:
    """One dispatched-but-not-collected heartbeat."""
    admitted: Dict[str, List[Ticket]]
    results: Any
    spans: _BeatSpans
    merged: Any = None          # sharded: device merge, launched at dispatch
    n_admitted: int = 0
    n_dirty: int = 0
    scan_path: str = "full"
    join_path: str = ""
    queue_wait_sum: float = 0.0   # admitted tickets' waits in the queue
    queue_wait_max: float = 0.0
    held_writes: int = 0          # writes put back for want of slots
    backend_ops: Dict[str, int] = dataclasses.field(default_factory=dict)


class SharedDBEngine:
    """The always-on global plan + admission queues."""

    def __init__(self, plan: CompiledPlan, update_slots: UpdateSlots,
                 initial_data: Dict[str, Dict[str, np.ndarray]],
                 jit: bool = True, kernels: str = "auto",
                 pipeline_depth: int = 2, delta_scans: bool = True,
                 delta_joins: bool = True, mesh=None):
        """``mesh``: an optional 1-D ``jax.sharding.Mesh`` — the always-on
        plan then runs SHARDED by spine-row range (core/sharding.py):
        row-sharded spine tables + carries, replicated join probe sides,
        shard-local delta beats, all-shard reseed beats, and an
        on-device cross-shard result merge launched at dispatch (collect
        is a device-to-host copy).  ``mesh=None`` (the default)
        is the existing single-device path, untouched; a 1-device mesh is
        bit-identical to it."""
        self.plan = plan
        self.update_slots = update_slots
        self._queues: Dict[str, collections.deque] = {
            name: collections.deque() for name in plan.templates}
        self._update_queue: collections.deque = collections.deque()
        self._ticket_ids = itertools.count()
        self._backend = resolve_backend(kernels)
        self._jit = jit
        self._mesh = mesh
        # measured once from the initial snapshot and reused by every
        # re-lower (folds): the partition geometry must stay identical
        # across generations for the carried key partitions to remap
        self._key_stats = _measure_key_stats(plan, initial_data)
        self.delta_scans = delta_scans
        self.delta_joins = delta_joins
        handle = self._build_compiled(plan)
        if handle.shard_spec is not None:
            from repro.core import sharding
            self.state = sharding.init_sharded_state(handle.shard_spec,
                                                     initial_data)
        else:
            self.state = plan.catalog.init_state(initial_data)
        self._install_handle(handle)
        self._fold: Optional[_PendingFold] = None
        self.folds_done = 0
        # set by a fold commit: the first post-fold heartbeat is a FORCED
        # full-rescan reseed under the new layout (the migration beat's
        # other half) — after it the engine is indistinguishable from a
        # cold engine compiled with the extended template set
        self._force_full = False
        self._carry = None           # previous heartbeat's scan words +
        #                              key partitions (donated halves)
        self._rid_carry = None       # previous heartbeat's join rids
        self._carry_token = None
        # (active, params) of the last DISPATCHED heartbeat: the delta
        # path diffs against these to find changed admission slots
        self._prev_params = np.zeros((plan.qcap, plan.n_params_max, 2),
                                     np.int32)
        self._prev_active = np.zeros((plan.qcap,), bool)
        self.pipeline_depth = max(1, pipeline_depth)
        # double-buffered admission: one staging set per pipeline slot
        self._staging = [_StagingBuffers(plan, update_slots)
                         for _ in range(self.pipeline_depth)]
        self._staging_idx = 0
        self._inflight: collections.deque[_InFlight] = collections.deque()
        # routing dicts from backpressure collects inside dispatch(),
        # surfaced by the next public collect() so no cycle's routed
        # tickets vanish from the return-value stream
        self._spilled: Dict[str, List[Ticket]] = {}
        self._spilled_stats: List[_InFlight] = []
        self.cycles_run = 0
        self._beat_seq = itertools.count()
        self.last_overflow = 0    # union-cap overflow of the last collect
        self.delta_cycles = 0     # heartbeats dispatched down each path
        self.full_cycles = 0
        self.delta_join_cycles = 0    # ... and down each JOIN path
        self.full_join_cycles = 0
        self.last_scan_path = ""  # paths of the last dispatch
        self.last_join_path = ""
        self.last_delta_overflow = 0   # defensive invariant (always 0)
        self.last_parts_rebuilt: Dict[str, bool] = {}
        self.last_collect_stats = self._collect_stats([])

    # --------------------------------------------- compiled-cycle handle
    def _build_compiled(self, plan: CompiledPlan) -> _CompiledHandle:
        """Lower + build + wrap one plan generation's three cycle
        flavours.  Pure with respect to the engine's serving state, so a
        background fold thread can run it while the installed generation
        keeps beating."""
        lowered = lower_plan(plan, key_stats=self._key_stats)
        # always-on planlint: the cheap IR passes gate EVERY generation
        # (cold start and every background fold build) before anything
        # compiles against its layout
        from repro.analysis_static.ir_passes import run_construction_passes
        run_construction_passes(lowered, key_stats=self._key_stats)
        # per-flavour backend-op launch counters
        # (CycleResult.backend_ops): each cycle flavour traces through
        # its own counting wrapper and clears its dict at traced-function
        # entry, so the counts always reflect the CURRENT trace's static
        # launch count per beat
        backend_ops: Dict[str, Dict[str, int]] = {
            "full": {}, "delta": {}, "delta_join": {}}
        cb = {f: counting_backend(self._backend, c)
              for f, c in backend_ops.items()}
        if self._mesh is not None:
            from repro.core import sharding
            spec = sharding.build_shard_spec(plan, self._mesh)
            cycle = sharding.build_sharded_cycle(lowered, cb["full"],
                                                 spec)
            delta = sharding.build_sharded_delta_cycle(lowered,
                                                       cb["delta"], spec)
            delta_j = sharding.build_sharded_delta_cycle(
                lowered, cb["delta_join"], spec, delta_joins=True)
            # cross-shard result routing runs ON DEVICE, launched at
            # dispatch right behind the cycle; collect only assembles
            device_merge, assemble = sharding.build_merge(lowered, spec)
            repl = spec.repl_sharding()
            stage = lambda a: jax.device_put(np.asarray(a), repl)  # noqa: E731
        else:
            spec = None
            cycle = build_cycle(lowered, cb["full"])
            delta = build_delta_cycle(lowered, cb["delta"])
            delta_j = build_delta_cycle(lowered, cb["delta_join"],
                                        delta_joins=True)
            device_merge, assemble = None, None
            stage = jnp.asarray
        cycle = _clear_counts_at_entry(cycle, backend_ops["full"],
                                       "beat_full")
        delta = _clear_counts_at_entry(delta, backend_ops["delta"],
                                       "beat_delta")
        delta_j = _clear_counts_at_entry(delta_j,
                                         backend_ops["delta_join"],
                                         "beat_delta_join")
        # donate storage: the snapshot rolls forward functionally in
        # place; the delta cycles additionally donate the carried scan
        # words + key partitions (each carry is produced by one heartbeat
        # and consumed by exactly the next, so in-flight cycles never
        # alias it).  The rid carry (arg 2 of the delta-join cycle) is
        # deliberately NOT donated: its arrays double as the previous
        # heartbeat's in-flight ``results["_join_rids"]``.
        donation: Dict[str, tuple] = {}
        if self._jit:
            donation = dict(DONATION_SPEC)
            cycle = jax.jit(cycle, donate_argnums=donation["full"])
            delta = jax.jit(delta, donate_argnums=donation["delta"])
            delta_j = jax.jit(delta_j,
                              donate_argnums=donation["delta_join"])
        # the admission layout this generation's carries live under: a
        # delta heartbeat must never consume a carry whose slot layout
        # differs (word windows, offsets and packed depth all bake into
        # the carried shapes/meanings), e.g. across a fold or an elastic
        # re-lower
        layout_token = (plan.qcap, plan.n_params_max,
                        tuple(sorted(plan.offsets.items())),
                        tuple(sorted(plan.caps.items())),
                        spec.n_shards if spec else 0)
        return _CompiledHandle(
            plan=plan, lowered=lowered, backend_ops=backend_ops,
            cycle=cycle, cycle_delta=delta, cycle_delta_join=delta_j,
            shard_spec=spec, device_merge=device_merge,
            assemble=assemble, stage=stage,
            # join stages with carried rid state (non-gather paths)
            carried_joins=tuple(j for j in lowered.joins
                                if j.kind != "gather"),
            layout_token=layout_token, donation=donation)

    def _install_handle(self, h: _CompiledHandle) -> None:
        """Atomically swap the serving generation (a beat boundary)."""
        self.plan = h.plan
        self._lowered = h.lowered
        self.backend_ops = h.backend_ops
        self._cycle = h.cycle
        self._cycle_delta = h.cycle_delta
        self._cycle_delta_join = h.cycle_delta_join
        self._shard_spec = h.shard_spec
        self._device_merge = h.device_merge
        self._assemble = h.assemble
        self._stage = h.stage
        self._carried_joins = h.carried_joins
        self._layout_token = h.layout_token
        # flavour -> argument shapes of its first dispatch under this
        # generation (``beat_hlo`` lowers each flavour from them again)
        self._beat_args: Dict[str, Any] = {}

    # ------------------------------------------------------ plan folding
    def begin_fold(self, new_templates: List[QueryTemplate],
                   new_caps: Dict[str, int],
                   background: bool = True) -> dict:
        """Fold new templates into the running plan (core/folding.py).

        Validates the extension synchronously (cheap — a recompile of
        the plan graph, no lowering), opens admission queues for the new
        templates immediately (their queries queue and are served after
        the fold commits), and builds + compiles the extended
        generation in a background thread while the current one keeps
        beating.  The swap happens at the next dispatch() after the
        build finishes: drain in-flight beats, install the new handle,
        migrate the carries, force one full-rescan reseed beat.

        Returns the structured drain -> re-lower -> resume recipe (the
        ``background`` variant of runtime/elastic.relower_recipe — the
        same machinery that drives elastic re-meshing).
        """
        from repro.runtime.elastic import relower_recipe
        if self._fold is not None:
            raise RuntimeError(
                "[planlint:fold-in-flight] a fold is already in flight "
                "— wait for it to commit before starting another "
                "(serving front ends batch registrations instead)")
        new_templates = list(new_templates)
        new_plan = folding.extend_plan(self.plan, new_templates,
                                       dict(new_caps))
        if self._shard_spec is not None:
            from repro.core import sharding
            sharding.check_fold_mirrors(self.plan, new_plan)
        for t in new_templates:
            self._queues.setdefault(t.name, collections.deque())
        fold = _PendingFold(plan=new_plan)
        self._fold = fold
        if background:
            fold.thread = threading.Thread(target=self._fold_build,
                                           args=(fold,),
                                           name="plan-fold", daemon=True)
            fold.thread.start()
        else:
            self._fold_build(fold)
        return relower_recipe(tuple(self.plan.templates),
                              tuple(new_plan.templates),
                              what="the extended always-on plan",
                              background=True)

    def fold_in_flight(self) -> bool:
        return self._fold is not None

    def fold_ready(self) -> bool:
        return self._fold is not None and self._fold.ready()

    def _fold_build(self, fold: _PendingFold) -> None:
        """Background half of a fold: lower, build, compile, warm.

        When it runs on the fold thread it denices itself first: the
        build is pure slack work (the old generation keeps serving and
        commits the swap whenever the build lands), so on a saturated
        host the serving beats keep the cores and the build fills the
        gaps — the cost of a fold is paid in fold LATENCY, never in
        serving-beat wall (the BENCH_PR8 gate)."""
        try:
            if fold.thread is not None:
                try:
                    os.setpriority(os.PRIO_PROCESS,
                                   threading.get_native_id(), 19)
                except (AttributeError, OSError):
                    pass    # non-Linux / restricted: build at normal prio
            handle = self._build_compiled(fold.plan)
            if self._jit:
                self._fold_warmup(handle)
            fold.handle = handle
        except BaseException as e:  # noqa: BLE001 — surfaced at commit
            fold.error = e
        finally:
            fold.built.set()

    def _fold_warmup(self, h: _CompiledHandle) -> None:
        """Populate the new generation's jit caches OFF the serving
        path: one dummy beat per cycle flavour, on throwaway state of
        the real shapes/shardings, so the migration beat pays a cache
        hit instead of a trace + XLA compile."""
        plan = h.plan
        if h.shard_spec is not None:
            from repro.core import sharding
            state = sharding.init_sharded_state(h.shard_spec, {})
        else:
            state = plan.catalog.init_state({})
        queries = {
            "params": h.stage(np.zeros(
                (plan.qcap, plan.n_params_max, 2), np.int32)),
            "active": h.stage(np.zeros((plan.qcap,), bool))}

        def batches():
            return jax.tree.map(h.stage, {
                t: empty_update_batch(schema, self.update_slots, xp=np)
                for t, schema in plan.catalog.schemas.items()})

        state, carry, results = h.cycle(state, queries, batches())
        rids = results["_join_rids"]
        dq = dict(queries, changed=h.stage(np.zeros((plan.qcap,), bool)))
        state, carry, _ = h.cycle_delta(state, carry, dq, batches())
        if h.carried_joins:
            state, carry, _ = h.cycle_delta_join(state, carry, rids, dq,
                                                 batches())
        jax.block_until_ready(state)

    def _commit_fold(self) -> None:
        """The migration beat boundary: swap generations atomically.

        Runs at dispatch() once the background build is ready.  In-flight
        beats drain first (their results are positional in the OLD
        layout), the new handle installs, the admission-diff state
        prefix-copies into the wider layout, and the carries migrate —
        routed through the same always-on carry/layout check as the
        delta dispatch path — before one forced full-rescan beat reseeds
        everything under the new layout."""
        fold, self._fold = self._fold, None
        if fold.thread is not None:
            fold.thread.join()
        if fold.error is not None:
            raise RuntimeError(
                f"background fold of {sorted(set(fold.plan.templates) - set(self.plan.templates))} "
                "failed to build") from fold.error
        while self._inflight:
            for name, tickets in self._collect_oldest().items():
                self._spilled.setdefault(name, []).extend(tickets)
        old_plan, old_lowered = self.plan, self._lowered
        self._install_handle(fold.handle)
        plan = self.plan
        # admission-diff state: the old slot ranges are a prefix of the
        # new layout, appended slots have never been admitted
        prev_p = np.zeros((plan.qcap, plan.n_params_max, 2), np.int32)
        prev_p[:old_plan.qcap, :old_lowered.n_params_max] = \
            self._prev_params
        prev_a = np.zeros((plan.qcap,), bool)
        prev_a[:old_plan.qcap] = self._prev_active
        self._prev_params, self._prev_active = prev_p, prev_a
        self._staging = [_StagingBuffers(plan, self.update_slots)
                         for _ in range(self.pipeline_depth)]
        self._staging_idx = 0
        carry, rids = folding.migrate_carry(
            old_lowered, self._lowered, self._carry, self._rid_carry)
        self._carry, self._rid_carry = carry, rids
        if carry is not None:
            # version the swap: the migrated carry now lives under the
            # NEW layout token, proven through the always-on guard
            self._carry_token = self._layout_token
            check_carry_layout(self._carry_token, self._layout_token)
        else:
            self._carry_token = None
        self._force_full = True
        self.folds_done += 1

    # ------------------------------------------------------------------ API
    def submit(self, template: str, params: Dict[str, Any]) -> Ticket:
        """params: {pred_index: (lo, hi)} inclusive int ranges."""
        t = self.make_ticket(template, params)
        self.submit_ticket(t)
        return t

    def make_ticket(self, template: str, params: Dict[str, Any]) -> Ticket:
        """Mint a ticket WITHOUT enqueueing it (serving front ends hold
        tickets for templates still waiting on a fold batch)."""
        return Ticket(next(self._ticket_ids), template, params,
                      time.perf_counter())

    def accepts(self, template: str) -> bool:
        """True iff the engine has an admission queue for the template
        (compiled in, or in/awaiting an in-flight fold)."""
        return template in self._queues

    def submit_ticket(self, ticket: Ticket) -> None:
        self._queues[ticket.template].append(ticket)

    def submit_update(self, table: str, kind: str, payload: Dict) -> None:
        """kind: insert | update | delete (payload per storage slots)."""
        self._update_queue.append((table, kind, payload))

    def pending(self) -> int:
        return (sum(len(q) for q in self._queues.values())
                + len(self._update_queue))

    def in_flight(self) -> int:
        return len(self._inflight)

    # ------------------------------------------------------------ one beat
    def _admit_queries(self, buf: _StagingBuffers, flight: _InFlight,
                       t_admit: float) -> None:
        """Drain the queues into the packed staging buffers.

        Fills each admitted query's static slot range in the shared
        [qcap, P_max, 2] / [qcap] buffers (``dispatch`` then stages BOTH
        with one copy each — a single H2D copy per heartbeat instead of
        one per template), and records in ``flight`` the admitted
        tickets and how long each waited in its queue: ``t_admit`` less
        its submit time."""
        params, active = buf.params, buf.active
        wait_sum = wait_max = 0.0
        for name, tpl in self.plan.templates.items():
            cap = self.plan.caps[name]
            off = self.plan.offsets[name]
            take: List[Ticket] = []
            q = self._queues[name]
            while q and len(take) < cap:
                take.append(q.popleft())
            for slot, ticket in enumerate(take):
                g = off + slot
                active[g] = True
                for pi in range(len(tpl.preds)):
                    lo, hi = ticket.params[pi]
                    params[g, pi, 0] = lo
                    params[g, pi, 1] = hi
                wait = t_admit - ticket.submit_time
                wait_sum += wait
                wait_max = max(wait_max, wait)
            flight.admitted[name] = take
        flight.n_admitted = sum(len(ts) for ts in flight.admitted.values())
        flight.queue_wait_sum, flight.queue_wait_max = wait_sum, wait_max

    def _admit_updates(self, buf: _StagingBuffers,
                       flight: _InFlight) -> Dict[str, int]:
        """Drain the update queue into the staging batches, in arrival
        order; a write whose table has no free slot of its kind is held
        for the next beat (counted in ``flight.held_writes``).  Returns
        the per-table admitted touch counts."""
        cat = self.plan.catalog
        s = self.update_slots
        np_batches = buf.updates
        fill = {t: {"ins": 0, "upd": 0, "del": 0} for t in cat.schemas}
        hold = collections.deque()
        while self._update_queue:
            table, kind, payload = self._update_queue.popleft()
            b, f = np_batches[table], fill[table]
            if kind == "insert":
                if f["ins"] >= s.n_insert:
                    hold.append((table, kind, payload))
                    continue
                i = f["ins"]
                for c, v in payload.items():
                    b["ins_rows"][c][i] = int(v)
                b["ins_mask"][i] = True
                f["ins"] += 1
            elif kind == "update":
                if f["upd"] >= s.n_update:
                    hold.append((table, kind, payload))
                    continue
                i = f["upd"]
                schema = cat.schemas[table]
                b["upd_key"][i] = int(payload["key"])
                b["upd_col"][i] = schema.columns.index(payload["col"])
                b["upd_val"][i] = int(payload["val"])
                b["upd_mask"][i] = True
                f["upd"] += 1
            else:
                if f["del"] >= s.n_delete:
                    hold.append((table, kind, payload))
                    continue
                i = f["del"]
                b["del_key"][i] = int(payload["key"])
                b["del_mask"][i] = True
                f["del"] += 1
        self._update_queue = hold
        flight.held_writes = len(hold)
        # per-table admitted touch counts: an exact upper bound on the
        # rows this batch can dirty (delta-path eligibility + accounting)
        touches = {t: f["ins"] + f["upd"] + f["del"]
                   for t, f in fill.items()}
        flight.n_dirty = sum(touches.values())
        return touches

    # -------------------------------------------------- incremental scans
    def _diff_admission(self, buf: _StagingBuffers) -> np.ndarray:
        """Changed-slot vector vs the previously dispatched heartbeat.

        A slot changed iff its activation flipped, or it stayed active
        with different parameters — exactly the columns of the carried
        scan words that the delta cycle's admission pane must refresh.
        """
        changed = buf.changed
        np.not_equal(buf.active, self._prev_active, out=changed)
        both = buf.active & self._prev_active
        if both.any():
            diff = (buf.params != self._prev_params).any(axis=(1, 2))
            np.logical_or(changed, both & diff, out=changed)
        return changed

    def _delta_eligible(self, changed: np.ndarray,
                        touches: Dict[str, int]) -> bool:
        """Host-side delta-path admission control (conservative).

        True iff every predicated scan's changed slots fit inside its
        CONTIGUOUS admission pane (span of changed words <= delta_words)
        and every table's batch fits its dirty set — so the traced delta
        cycle can assume its fixed delta capacities suffice and never
        needs a data-dependent fallback branch.
        """
        schemas = self.plan.catalog.schemas
        for table, n in touches.items():
            if n > schemas[table].dirty_cap:
                return False
        for st in self._lowered.scans:
            if not st.cols:
                continue
            sc = changed[st.wlo * 32:st.whi * 32] & st.covered
            words = np.flatnonzero(sc.reshape(-1, 32).any(axis=1))
            if words.size and words[-1] - words[0] + 1 > st.delta_words:
                return False
        return True

    def _join_delta_eligible(self, touches: Dict[str, int]) -> bool:
        """Host-side delta-JOIN admission control (conservative).

        True iff the plan has carried join stages, a rid carry exists,
        and NO carried stage's PK table was touched this heartbeat — a
        touched PK side rebuilds its partitions
        (storage.refresh_key_partitions), which can move/retire the rows
        the carried rids point at.  Spine-side dirty capacity is already
        guaranteed by ``_delta_eligible`` (it bounds every table's
        touches), so the delta probe's dirty set is exact.
        """
        if not self._carried_joins or self._rid_carry is None:
            return False
        return all(touches[j.pk_table] == 0 for j in self._carried_joins)

    def dispatch(self) -> None:
        """Admit one heartbeat's work and launch the global plan.

        Returns as soon as the computation is dispatched (JAX async);
        results are claimed by a later collect().  At full pipeline depth
        the oldest in-flight cycle is collected first (backpressure), so
        at most ``pipeline_depth`` cycles are ever outstanding — which
        also makes staging-buffer reuse safe: a buffer is only rewritten
        after the cycle that consumed it has completed.
        """
        if self._fold is not None and self._fold.ready():
            # migration beat boundary: the background build finished —
            # swap generations before admitting this heartbeat's work
            self._commit_fold()
        while len(self._inflight) >= self.pipeline_depth:
            for name, tickets in self._collect_oldest().items():
                self._spilled.setdefault(name, []).extend(tickets)
        spans = _BeatSpans(next(self._beat_seq))
        flight = _InFlight({}, None, spans=spans)
        buf = self._staging[self._staging_idx]
        self._staging_idx = (self._staging_idx + 1) % len(self._staging)
        with spans("beat.admit") as t_admit:
            with spans("beat.fill"):
                buf.reset()
                self._admit_queries(buf, flight, t_admit)
                touches = self._admit_updates(buf, flight)
                # incremental-scan path choice, made HOST-side so the
                # traced delta cycle never contains the full-table
                # compare: eligible when the carried words exist and
                # every delta fits its fixed capacity, else a safe full
                # rescan (which reseeds the carry)
                changed = self._diff_admission(buf)
                force_full, self._force_full = self._force_full, False
                use_delta = (not force_full and self.delta_scans
                             and self._carry is not None
                             and self._delta_eligible(changed, touches))
                use_delta_join = (use_delta and self.delta_joins
                                  and self._join_delta_eligible(touches))
            with spans("beat.h2d"):
                queries = {"params": self._stage(buf.params),
                           "active": self._stage(buf.active)}
                if use_delta:
                    queries["changed"] = self._stage(changed)
                updates = jax.tree.map(self._stage, buf.updates)
        if use_delta:
            # carry-invalidation audit: a delta heartbeat must never
            # consume a carry produced under a different admission
            # layout (the carried words/rids are positional in it); a
            # full-rescan heartbeat reseeds BOTH halves below, so the
            # token always matches unless the plan was re-lowered
            # without resetting the carries.  An always-on RuntimeError,
            # not an assert: ``python -O`` must not strip it.
            check_carry_layout(self._carry_token, self._layout_token)
            if use_delta_join:
                flavour, cycle = "delta_join", self._cycle_delta_join
                args = (self.state, self._carry, self._rid_carry, queries,
                        updates)
            else:
                flavour, cycle = "delta", self._cycle_delta
                args = (self.state, self._carry, queries, updates)
        else:
            flavour, cycle = "full", self._cycle
            args = (self.state, queries, updates)
        if self._jit and flavour not in self._beat_args:
            self._beat_args[flavour] = jax.tree.map(_arg_spec, args)
        with spans("beat.launch"):
            self.state, self._carry, results = cycle(*args)
            if self._device_merge is not None:
                # launch the on-device cross-shard merge right behind
                # the cycle (async); collect only blocks + copies
                flight.merged = self._device_merge(results["_shard"])
        flight.results = results
        if use_delta:
            self.delta_cycles += 1
        else:
            self.full_cycles += 1
        # both carry halves are (re)seeded by EVERY heartbeat: the
        # scan/parts half from the cycle's carry output, the rid half
        # from the results (full-probe heartbeats — including every full
        # rescan — return freshly probed rids for all spine rows)
        self._rid_carry = results["_join_rids"]
        self._carry_token = self._layout_token
        self.last_scan_path = "delta" if use_delta else "full"
        if self._carried_joins:
            self.last_join_path = "delta" if use_delta_join else "full"
            if use_delta_join:
                self.delta_join_cycles += 1
            else:
                self.full_join_cycles += 1
        self._prev_params[...] = buf.params
        self._prev_active[...] = buf.active
        flight.scan_path = self.last_scan_path
        flight.join_path = self.last_join_path
        flight.backend_ops = dict(self.backend_ops[flavour])
        self._inflight.append(flight)

    def collect(self) -> Dict[str, List[Ticket]]:
        """Block on the oldest in-flight heartbeat and route its results.

        Also surfaces any routing spilled by dispatch()-side
        backpressure, so every admitted ticket appears in exactly one
        collect() return.  ``last_collect_stats`` aggregates the
        surfaced heartbeats' admitted/dirty counts and scan path for the
        caller's CycleResult accounting."""
        out, self._spilled = self._spilled, {}
        for name, tickets in self._collect_oldest().items():
            out.setdefault(name, []).extend(tickets)
        stats, self._spilled_stats = self._spilled_stats, []
        self.last_collect_stats = self._collect_stats(stats)
        return out

    @staticmethod
    def _collect_stats(stats: List[_InFlight]) -> Dict[str, Any]:
        """The counters of the heartbeats one collect surfaced.  The
        ``t_*_s`` phase times and ``h2d_s`` are span lengths; ``spans``
        holds every span as ``(beat seq, name, start, end)``."""
        def one_path(paths):
            paths = {p for p in paths if p}
            return (paths.pop() if len(paths) == 1
                    else "mixed" if paths else "")

        def span_s(name):
            return sum(f.spans.seconds(name) for f in stats)

        ops: Dict[str, int] = {}
        for f in stats:
            for op, n in f.backend_ops.items():
                ops[op] = ops.get(op, 0) + n
        return {
            "admitted": sum(f.n_admitted for f in stats),
            "dirty": sum(f.n_dirty for f in stats),
            "scan_path": one_path(f.scan_path for f in stats),
            "join_path": one_path(f.join_path for f in stats),
            "t_stage_s": span_s("beat.admit"),
            "t_dispatch_s": span_s("beat.launch"),
            "t_kernel_s": span_s("beat.wait"),
            "t_collect_s": span_s("beat.route"),
            "h2d_s": span_s("beat.h2d"),
            "queue_wait_s_sum": sum(f.queue_wait_sum for f in stats),
            "queue_wait_s_max": max((f.queue_wait_max for f in stats),
                                    default=0.0),
            "held_writes": sum(f.held_writes for f in stats),
            "spans": [(f.spans.seq, *sp) for f in stats
                      for sp in f.spans.spans],
            "backend_ops": ops}

    def _collect_oldest(self) -> Dict[str, List[Ticket]]:
        if not self._inflight:
            return {}
        flight = self._inflight.popleft()
        self._spilled_stats.append(flight)
        results = flight.results
        with flight.spans("beat.wait"):
            jax.block_until_ready(results)
            if flight.merged is not None:
                jax.block_until_ready(flight.merged)
        with flight.spans("beat.route"):
            if self._assemble is not None:
                # sharded heartbeat: the cross-shard routing pass already
                # ran on-device (launched at dispatch); assembling the
                # final per-template results is a device-to-host copy +
                # passthrough
                results = self._assemble(results, flight.merged)
            self.last_overflow = int(results["_overflow"])
            # full-rescan heartbeats have no delta capacities to violate,
            # so the invariant reads 0 rather than a stale delta-cycle
            # value
            self.last_delta_overflow = int(results.get("_delta_overflow",
                                                       0))
            self.last_parts_rebuilt = {
                t: bool(v) for t, v in results["_parts_rebuilt"].items()}
            now = time.perf_counter()
            out = {}
            for name, tickets in flight.admitted.items():
                res = jax.tree.map(np.asarray, results[name])
                for slot, ticket in enumerate(tickets):
                    ticket.result = jax.tree.map(lambda a: a[slot], res)
                    ticket.done_time = now
                out[name] = tickets
        self.cycles_run += 1
        return out

    def run_cycle(self) -> Dict[str, List[Ticket]]:
        """One synchronous heartbeat: dispatch then drain all in-flight."""
        self.dispatch()
        out: Dict[str, List[Ticket]] = {}
        while self._inflight:
            for name, tickets in self.collect().items():
                out.setdefault(name, []).extend(tickets)
        return out

    def run_until_drained(self, max_cycles: int = 1000,
                          pipelined: bool = False) -> List[CycleResult]:
        """Cycle until the queues are empty.

        ``max_cycles`` bounds cycles COLLECTED (each return entry is one
        completed heartbeat), not dispatches — dispatching is likewise
        capped by the budget so no admitted work is left un-collected
        when the bound trips.  Returns one ``CycleResult`` (routed
        tickets + observed wall time) per collected cycle, for latency
        accounting.

        pipelined=True keeps up to ``pipeline_depth`` heartbeats in
        flight, overlapping admission/staging for cycle N+1 with device
        execution of cycle N.
        """
        depth = self.pipeline_depth if pipelined else 1
        done: List[CycleResult] = []
        dispatched = 0
        t_prev = time.perf_counter()
        while len(done) < max_cycles and (self.pending() or self._inflight
                                          or self._spilled):
            while (self.pending() and dispatched < max_cycles
                   and len(self._inflight) < depth):
                self.dispatch()
                dispatched += 1
            if not self._inflight and not self._spilled:
                break       # budget exhausted with work still queued
            routed = self.collect()
            now = time.perf_counter()
            s = self.last_collect_stats
            done.append(CycleResult(tickets=routed, wall_s=now - t_prev,
                                    admitted=s["admitted"],
                                    dirty=s["dirty"],
                                    scan_path=s["scan_path"],
                                    join_path=s["join_path"],
                                    t_stage_s=s["t_stage_s"],
                                    t_dispatch_s=s["t_dispatch_s"],
                                    t_kernel_s=s["t_kernel_s"],
                                    t_collect_s=s["t_collect_s"],
                                    backend_ops=s["backend_ops"]))
            t_prev = now
        return done

    # ------------------------------------------------------------ tracing
    def beat_hlo(self) -> Dict[str, str]:
        """{module name: compiled HLO text} of each cycle flavour the
        installed generation has dispatched; empty when unjitted.

        Each flavour is lowered again from the argument shapes of its
        first dispatch and compiled — a hit in JAX's compilation cache
        where one is on, else a compile of its own — so call it off the
        serving path."""
        cycles = {"full": self._cycle, "delta": self._cycle_delta,
                  "delta_join": self._cycle_delta_join}
        out = {}
        for flavour, specs in self._beat_args.items():
            text = cycles[flavour].lower(*specs).compile().as_text()
            # the text opens "HloModule <name>, ..."
            out[text.split(",", 1)[0].split()[1]] = text
        return out

    def trace_export(self) -> Dict[str, Dict[str, str]]:
        """{module name: {HLO instruction name: ``"<stage>/<detail>"``}}
        for each dispatched cycle flavour (``beat_hlo``): the heartbeat
        stage (``lowering.STAGES``) of every device op a profiler trace
        of this engine can show, by the op's instruction name."""
        return {m: stage_map(text) for m, text in self.beat_hlo().items()}

    # --------------------------------------------------- host-side fetch
    def snapshot(self, table: str) -> Dict[str, np.ndarray]:
        """Host view of a table's columns/validity at the ORIGINAL
        (unpadded) capacity.  The sharded state keeps columns as flat
        row-major leaves, so the same read works for the single-device,
        row-sharded and replicated layouts alike."""
        schema = self.plan.catalog.schemas[table]
        t = self.state[table]
        T = schema.capacity
        out = {c: np.asarray(t[c])[:T] for c in schema.columns}
        out["_valid"] = np.asarray(t["_valid"])[:T]
        out["_n"] = int(t["_n"])
        return out

    def materialize(self, table: str, row_ids: np.ndarray,
                    cols: Optional[List[str]] = None) -> Dict[str, np.ndarray]:
        """Fetch tuples by row id from the current snapshot (result
        delivery — the Output operator of Fig. 5)."""
        t = self.state[table]
        schema = self.plan.catalog.schemas[table]
        cols = cols or list(schema.columns)
        ids = np.asarray(row_ids)
        safe = np.clip(ids, 0, schema.capacity - 1)
        out = {c: np.where(ids >= 0, np.asarray(t[c])[safe], 0)
               for c in cols}
        out["_row"] = ids
        return out
