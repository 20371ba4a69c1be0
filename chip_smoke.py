"""Chip smoke test: the SharedDB heartbeat end to end on a TPU.

    python chip_smoke.py              # one chip: both TPC-W catalog variants
    python chip_smoke.py --chips 4    # only the sharded phase, on 4 chips

One chip: TPC-W at 10,000 items x 100 emulated browsers (the TPC-W v1.8
clause-4 population: 288,000 customers, 259,200 orders, ~777,600 order
lines), built from ``--seed``, served by ``SharedDBEngine`` with
``kernels="auto"`` and ``jit=True`` — the compiled Pallas kernels — for
both catalog variants in turn: the dense-PK default and
``dense_pk_index=False``, whose joins run the partitioned probe and the
fused kernel's probe steps.  A stream of shopping-mix interactions goes
through ``dispatch()``/``collect()``: mix beats of several interactions,
then a trickle of cart interactions whose slot-stable admission takes
the delta paths.  Every ticket is checked against the query-at-a-time
oracle (``core/baseline.py``) on the same data and updates.

``--chips 4``: the index-less engine on a 4-shard row mesh, the same
stream checked ticket for ticket against a one-chip engine on device 0
in the same process and against the oracle.

Progress goes to earlier lines; the last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The script exits non-zero, printing no result, when JAX finds no TPU,
when it is run outside a checkout of the repo, or when any check fails.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

ITEMS = 10000
EMULATED_BROWSERS = 100
CUSTOMERS = 2880 * EMULATED_BROWSERS     # TPC-W clause 4.3
MIX = "shopping"
MIX_BEATS, MIX_PER_BEAT = 6, 4
TRICKLE = ("buy_request", "shopping_cart") * 4
MIN_DELTA_BEATS = 3
STEADY_OPS = {"fused_delta", "groupby"}   # one fused launch + group-by


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# Instrumentation: compile seconds and persistent-cache hits
# ---------------------------------------------------------------------------


class CompileStats:
    """Backend compile seconds and persistent-cache hits/misses, from
    JAX's monitoring events."""

    def __init__(self):
        import jax.monitoring as mon
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)

    def snapshot(self):
        return (self.seconds, self.hits, self.misses)

    def since(self, snap) -> str:
        s, h, m = snap
        return (f"compile {self.seconds - s:.1f} s (persistent cache "
                f"hits {self.hits - h}, misses {self.misses - m})")


def peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


# ---------------------------------------------------------------------------
# The workload stream and the oracle check
# ---------------------------------------------------------------------------


def make_stream(seed: int, items: int, customers: int):
    """Beats of interactions: shopping-mix beats, then the cart trickle
    (slot-stable admission -> delta beats)."""
    import numpy as np
    from repro.workloads import tpcw
    gen = tpcw.WorkloadGenerator(np.random.default_rng(seed + 1), items,
                                 customers)
    mix = gen.sample_mix(MIX, MIX_BEATS * MIX_PER_BEAT)
    beats = [mix[i:i + MIX_PER_BEAT]
             for i in range(0, len(mix), MIX_PER_BEAT)]
    beats += [[gen.interaction(kind)] for kind in TRICKLE]
    return beats


def same_result(got, want) -> bool:
    """Rows as sets (shared routing and the oracle agree on membership
    at every limit), group scores as sorted arrays."""
    import numpy as np
    if "rows" in want:
        a = {int(x) for x in np.asarray(got["rows"]) if x >= 0}
        b = {int(x) for x in np.asarray(want["rows"]) if x >= 0}
        return a == b
    return np.allclose(np.sort(np.asarray(got["scores"])),
                       np.sort(np.asarray(want["scores"])), rtol=1e-6)


class PathCounts:
    def __init__(self):
        self.scan = {}
        self.join = {}
        self.delta_ops = {}

    def add(self, stats) -> None:
        sp, jp = stats["scan_path"], stats["join_path"]
        self.scan[sp] = self.scan.get(sp, 0) + 1
        if jp:
            self.join[jp] = self.join.get(jp, 0) + 1
        if sp == "delta":
            key = json.dumps(stats["backend_ops"], sort_keys=True)
            self.delta_ops[key] = self.delta_ops.get(key, 0) + 1

    def line(self) -> str:
        return (f"beats by scan path {self.scan}, by join path "
                f"{self.join}; delta-beat backend_ops {self.delta_ops}")


def drive(engines, oracle, stream):
    """Run the stream through every engine beat by beat and check each
    ticket against the oracle (and against the first engine's ticket).
    Returns per-engine PathCounts and the number of tickets checked."""
    counts = {label: PathCounts() for label in engines}
    checked = 0
    for beat in stream:
        tickets = {label: [] for label in engines}
        for inter in beat:
            for table, kind, payload in inter.updates:
                oracle.apply_update(table, kind, payload)
                for eng in engines.values():
                    eng.submit_update(table, kind, payload)
            for name, params in inter.queries:
                for label, eng in engines.items():
                    tickets[label].append(eng.submit(name, params))
        for label, eng in engines.items():
            while eng.pending() or eng.in_flight():
                eng.dispatch()
                eng.collect()
                counts[label].add(eng.last_collect_stats)
        first = next(iter(engines))
        for i, ref_t in enumerate(tickets[first]):
            want = oracle.execute(ref_t.template, ref_t.params).result
            for label in engines:
                t = tickets[label][i]
                check(t.result is not None,
                      f"{label}: ticket {t.template} was never answered")
                check(same_result(t.result, want),
                      f"{label}: {t.template}{t.params} differs from the "
                      f"query-at-a-time oracle")
                check(same_result(t.result, ref_t.result),
                      f"{label}: {t.template}{t.params} differs from "
                      f"{first}")
            checked += 1
    return counts, checked


def check_paths(label: str, pc: PathCounts, joins: bool) -> None:
    check(pc.scan.get("full", 0) >= 1, f"{label}: no full-rescan beat")
    check(pc.scan.get("delta", 0) >= MIN_DELTA_BEATS,
          f"{label}: {pc.scan.get('delta', 0)} scan-delta beats, want "
          f">= {MIN_DELTA_BEATS}")
    if joins:
        check(pc.join.get("delta", 0) >= MIN_DELTA_BEATS,
              f"{label}: {pc.join.get('delta', 0)} join-delta beats, want "
              f">= {MIN_DELTA_BEATS}")
    for key in pc.delta_ops:
        ops = json.loads(key)
        check(ops.get("fused_delta") == 1 and set(ops) <= STEADY_OPS,
              f"{label}: a delta beat launched {ops}, want one fused_delta "
              f"(+ group-by)")


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def build(dense: bool, seed: int, items: int, customers: int):
    import numpy as np
    from repro.workloads import tpcw
    plan = tpcw.build_tpcw_plan(items, customers, dense_pk_index=dense)
    data = tpcw.generate_data(np.random.default_rng(seed), items,
                              customers)
    return plan, data


def oracle_for(seed: int, items: int, customers: int):
    """The query-at-a-time oracle over the dense-PK catalog: capacities
    and row placement equal both variants', and its joins are plain index
    gathers independent of the code under test."""
    from repro.core.baseline import QueryAtATimeEngine
    plan, data = build(True, seed, items, customers)
    return QueryAtATimeEngine(plan, data)


def one_chip_phase(seed: int, device, cstats: CompileStats) -> None:
    from repro.core.executor import SharedDBEngine
    from repro.workloads import tpcw
    for dense in (True, False):
        label = "dense-pk" if dense else "index-less"
        snap = cstats.snapshot()
        t0 = time.perf_counter()
        plan, data = build(dense, seed, ITEMS, CUSTOMERS)
        caps = {t: s.capacity for t, s in plan.catalog.schemas.items()}
        log(f"[{label}] {ITEMS} items x {EMULATED_BROWSERS} EBs "
            f"({CUSTOMERS} customers, {len(data['orders']['o_id'])} "
            f"orders, {len(data['order_line']['ol_o_id'])} order lines); "
            f"table capacities {caps}")
        eng = SharedDBEngine(plan, tpcw.DEFAULT_UPDATE_SLOTS, data,
                             kernels="auto", jit=True)
        check(eng._backend.name == "pallas",
              f"{label}: kernels='auto' resolved to {eng._backend.name}")
        oracle = oracle_for(seed, ITEMS, CUSTOMERS)
        log(f"[{label}] loaded in {time.perf_counter() - t0:.1f} s")
        t0 = time.perf_counter()
        counts, checked = drive({label: eng}, oracle,
                                make_stream(seed, ITEMS, CUSTOMERS))
        pc = counts[label]
        log(f"[{label}] {pc.line()}")
        check_paths(label, pc, joins=not dense)
        log(f"[{label}] {checked} tickets equal to the query-at-a-time "
            f"oracle; stream {time.perf_counter() - t0:.1f} s, "
            f"{cstats.since(snap)}; peak_bytes_in_use "
            f"{peak_bytes(device)}")
        del eng, oracle
        gc.collect()


def four_chip_phase(seed: int, devices, cstats: CompileStats) -> None:
    import jax
    from repro.core.executor import SharedDBEngine
    from repro.core.sharding import make_row_mesh
    from repro.workloads import tpcw
    check(len(devices) >= 4, f"--chips 4 needs 4 devices, JAX reports "
          f"{len(devices)}")
    snap = cstats.snapshot()
    t0 = time.perf_counter()
    plan, data = build(False, seed, ITEMS, CUSTOMERS)
    mesh = make_row_mesh(4)
    sharded = SharedDBEngine(plan, tpcw.DEFAULT_UPDATE_SLOTS, data,
                             kernels="auto", jit=True, mesh=mesh)
    single = SharedDBEngine(plan, tpcw.DEFAULT_UPDATE_SLOTS, data,
                            kernels="auto", jit=True)
    oracle = oracle_for(seed, ITEMS, CUSTOMERS)
    for label, eng in (("sharded", sharded), ("single", single)):
        check(eng._backend.name == "pallas",
              f"{label}: kernels='auto' resolved to {eng._backend.name}")
    col = sharded.state["order_line"]["ol_o_id"]
    shard_devices = {s.device for s in col.addressable_shards}
    shard_rows = sorted({s.data.shape[0] for s in col.addressable_shards})
    check(shard_devices == set(devices[:4]),
          f"order_line shards on {shard_devices}, want 4 devices")
    check(len(shard_rows) == 1 and shard_rows[0] * 4 == col.shape[0],
          f"order_line shard rows {shard_rows} do not split {col.shape[0]}")
    single_devices = {d for leaf in jax.tree.leaves(single.state)
                      for d in leaf.devices()}
    check(single_devices == {devices[0]},
          f"one-chip engine state on {single_devices}")
    log(f"[sharded] index-less TPC-W {ITEMS} items x {EMULATED_BROWSERS} "
        f"EBs on mesh {dict(mesh.shape)}; order_line {col.shape[0]} rows "
        f"as 4 shards of {shard_rows[0]} on {sorted(str(d) for d in shard_devices)}; "
        f"one-chip twin on {devices[0]}; loaded in "
        f"{time.perf_counter() - t0:.1f} s")
    t0 = time.perf_counter()
    counts, checked = drive({"sharded": sharded, "single": single}, oracle,
                            make_stream(seed, ITEMS, CUSTOMERS))
    for label, pc in counts.items():
        log(f"[{label}] {pc.line()}")
        check_paths(label, pc, joins=True)
    log(f"[sharded] {checked} tickets equal to the one-chip engine and to "
        f"the query-at-a-time oracle; stream "
        f"{time.perf_counter() - t0:.1f} s, {cstats.since(snap)}; "
        f"peak_bytes_in_use per device "
        f"{[peak_bytes(d) for d in devices[:4]]}")


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded phase, on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise SmokeFailure(f"no repro package under {SRC}: run this script "
                           "from a checkout of the repo")
    forced = os.environ.get("REPRO_KERNELS", "")
    if forced not in ("", "pallas", "auto"):
        raise SmokeFailure(f"REPRO_KERNELS={forced!r} would swap the "
                           "kernel backend; unset it or use 'pallas'")
    sys.path.insert(0, SRC)

    import jax
    from repro.runtime.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SmokeFailure(f"no TPU found: JAX reports {len(devices)} "
                           f"{platform} device(s)")
    import repro.kernels
    from repro.core.backends import resolve_backend

    backend = resolve_backend("auto").name
    interpret = repro.kernels.interpret_mode()
    log(f"device: {platform} {devices[0].device_kind} x{len(devices)}; "
        f"jax {jax.__version__}; compile cache {cache_dir}")
    log(f"backend: {backend} (interpret={interpret})")
    check(backend == "pallas", f"kernels='auto' resolved to {backend}")
    check(interpret is False, "Pallas kernels would run interpreted")

    cstats = CompileStats()
    if args.chips == 4:
        four_chip_phase(args.seed, devices, cstats)
    else:
        one_chip_phase(args.seed, devices[0], cstats)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        sys.exit(1)
