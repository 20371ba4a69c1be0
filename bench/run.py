#!/usr/bin/env python3
"""SharedDB benchmark: one cell of ``BENCHMARK.json`` on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout of the repo, on a machine with a TPU.
One process builds the cell's TPC-W deployment from ``--seed`` (the
configuration file names its sizes), serves it with ``SharedDBEngine``
(``kernels="auto"``, ``jit=True``, two beats in flight), warms every
heartbeat flavour the traffic can reach (full, delta, delta-join), then
lets the traffic file's emulated browsers drive ``dispatch()`` /
``collect()`` for ``--seconds``.  After the window it reads the tables
back, frees the engine, and replays the run through the plain reference
(``reference.py``) to decide ``correct``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its limit.
The same comparisons end standard error.  Without a TPU, with fewer chips
than the cell asks for, or outside a checkout that holds the program, it
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import heapq  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

from reference import (Reference, differs,  # noqa: E402
                       table_cells_differing)

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")
# JAX's persistent compilation cache: the one the environment names, else
# a fixed directory inside the checkout, so only a cell's first run compiles
CACHE_DIR = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
    os.path.join(ROOT, ".jax_cache")
GRACE_S = 60.0          # how long past the close an answer may still come
SAMPLE_PER_STRATUM = 200  # answers checked per (template, beat flavour)


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, no program, bad file)."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# The cell, from BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------


class Cell:
    def __init__(self, root: str, name: str):
        path = os.path.join(root, "BENCHMARK.json")
        try:
            with open(path) as f:
                bench = json.load(f)
        except OSError as e:
            raise BenchError(f"cannot read {path}: {e}") from e
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise BenchError(f"no workload {name!r} in BENCHMARK.json "
                             f"(have {sorted(cells)})")
        self.name = name
        self.spec = cells[name]
        self.chips = int(self.spec["chips"])
        configs = {c["name"]: c for c in bench["configs"]}
        self.config = _load_json(os.path.join(
            root, configs[self.spec["config"]]["file"]))
        self.traffic = _load_json(os.path.join(
            root, "bench", "traffic", self.spec["traffic"] + ".json"))

        def mine(m):
            return name in m.get("workloads", [name])
        self.end_to_end = [m for m in bench["end_to_end"] if mine(m)]
        self.per_layer = [m for m in bench["per_layer"] if mine(m)]


def _load_json(path: str) -> Dict:
    try:
        with open(path) as f:
            return json.load(f)
    except OSError as e:
        raise BenchError(f"cannot read {path}: {e}") from e


def load_reader(name: str):
    """The metric's reader, ``bench/metrics/<name>.py``: ``read(run)``
    returns the value, or None where the run holds nothing to read."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    if spec is None or not os.path.exists(path):
        raise BenchError(f"no reader {path} for metric {name!r}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# JAX: cache, devices, compile counts
# ---------------------------------------------------------------------------


def init_jax(require_tpu: bool, chips: int):
    import jax
    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX reports {len(devices)} "
                         f"{devices[0].platform} device(s)")
    if require_tpu and len(devices) < chips:
        raise BenchError(f"the cell asks for {chips} chips, JAX reports "
                         f"{len(devices)}")
    return devices


class CompileStats:
    """Backend compile seconds and persistent-cache hits/misses, from
    JAX's monitoring events (listeners live for the process)."""

    def __init__(self):
        import jax.monitoring as mon
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        self.compiles = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)

    def snapshot(self):
        return (self.seconds, self.hits, self.misses, self.compiles)


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------


def build_engine(config: Dict, data):
    """The program's TPC-W plan and engine for this configuration, after
    checking that the plan is the one the configuration file states."""
    from repro.core.executor import SharedDBEngine
    from repro.core.storage import UpdateSlots
    from repro.workloads import tpcw
    plan = tpcw.build_tpcw_plan(
        config["items"], config["customers"],
        max_results=config["max_results"], headroom=config["headroom"],
        dense_pk_index=config["dense_pk_index"])
    check_plan(plan, config)
    s = config["update_slots"]
    slots = UpdateSlots(n_insert=s["insert"], n_update=s["update"],
                        n_delete=s["delete"])
    return SharedDBEngine(plan, slots, data, jit=True, kernels="auto",
                          pipeline_depth=config["pipeline_depth"])


def check_plan(plan, config: Dict) -> None:
    """Refuse to measure a plan that is not the configuration's."""
    bad = []
    if plan.max_results != config["max_results"]:
        bad.append(f"max_results {plan.max_results}")
    schemas = plan.catalog.schemas
    for name, t in config["tables"].items():
        s = schemas.get(name)
        if s is None or list(s.columns) != t["columns"] \
                or s.capacity != t["capacity"] or s.pk != t.get("pk") \
                or (s.pk is not None
                    and s.indexed != config["dense_pk_index"]):
            bad.append(f"table {name}")
    if set(schemas) != set(config["tables"]):
        bad.append("table set")
    for name, t in config["templates"].items():
        p = plan.templates.get(name)
        if p is None:
            bad.append(f"template {name} missing")
            continue
        got = {"spine": p.spine,
               "preds": [[x.table, x.col] for x in p.preds],
               "joins": [[j.fk_col, j.pk_table] for j in p.joins],
               "cap": plan.caps[name]}
        want = {"spine": t["spine"], "preds": t["preds"],
                "joins": t.get("joins", []), "cap": t["cap"]}
        if p.group is not None:
            g = p.group
            got["group"] = {"by": g.group_col, "sum": g.agg_col,
                            "top": g.top_k, "groups": g.n_groups,
                            "order_by": g.order_by}
            want["group"] = dict(t.get("group") or {}, order_by="sum")
        else:
            got.update(sort=p.sort_col, desc=p.sort_desc, limit=p.limit)
            want.update(sort=t.get("sort"), desc=bool(t.get("desc")),
                        limit=t["limit"])
        if got != want:
            bad.append(f"template {name}: program {got} != file {want}")
    if set(plan.templates) != set(config["templates"]):
        bad.append("template set")
    if bad:
        raise BenchError("the program's plan differs from "
                         f"{config['name']}: {bad}")


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


class RunView:
    """What the metric readers see of one run."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def warm_up(loop, gen, kinds: List[str]) -> List[str]:
    """Reach every heartbeat flavour once, so nothing compiles in the
    window: a beat of one interaction of each kind (a full rescan), the
    same queries again with no writes (a delta beat; on an index-less
    catalog with the carried join rids, the delta-join beat), and again
    with one write to the item table, a PK side of the carried joins (a
    delta beat whose joins re-probe in full)."""
    from tpcw_gen import Interaction
    now = time.perf_counter()
    batch = [gen.interaction(k) for k in kinds]
    queries = [q for it in batch for q in it.queries]
    writes = [w for it in batch for w in it.writes]
    item_write = gen.interaction("admin_confirm").writes
    paths = []
    for w in (writes, [], item_write):
        loop.submit(Interaction("warm_up", queries, w), -1, now)
        s = loop.beat_sync().stats
        paths.append(f"{s['scan_path']}/{s['join_path'] or '-'}")
    return paths


class TicketSample:
    """The answers to check: up to ``k`` per (template, beat flavour)
    stratum, drawn from the seed as the beats are routed (the ``k``
    smallest of a priority drawn for each ticket), over every beat the
    run collects, warm-up included, so that every template is checked on
    every flavour of heartbeat the run reached.  No other answer is kept."""

    CHUNK = 1 << 16

    def __init__(self, rng: np.random.Generator,
                 k: int = SAMPLE_PER_STRATUM):
        self.rng, self.k = rng, k
        self._prio: List[float] = []
        self._heaps: Dict[tuple, list] = {}

    def __call__(self, b: int, stats: Dict, routed: Dict[str, list]) -> None:
        flavour = (stats["scan_path"], stats["join_path"])
        for name, tickets in routed.items():
            heap = self._heaps.setdefault((name, flavour), [])
            for t in tickets:
                if not self._prio:
                    self._prio = self.rng.random(self.CHUNK).tolist()
                item = (-self._prio.pop(), t.id, b, t)
                if len(heap) < self.k:
                    heapq.heappush(heap, item)
                elif item[0] > heap[0][0]:
                    heapq.heapreplace(heap, item)

    def by_beat(self) -> Dict[int, list]:
        out: Dict[int, list] = {}
        for _, heap in sorted(self._heaps.items(),
                              key=lambda kv: repr(kv[0])):
            for _, _, b, t in sorted(heap, key=lambda x: x[1]):
                out.setdefault(b, []).append(t)
        return out


class GcPauses:
    """The garbage collector's passes while armed, as (generation,
    seconds) on the host's clock."""

    def __init__(self):
        self.armed = False
        self.pauses: List[tuple] = []
        self._t = 0.0
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: Dict) -> None:
        if not self.armed:
            return
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))

    def close(self) -> Dict:
        gc.callbacks.remove(self._on_gc)
        full = [s for g, s in self.pauses if g == 2]
        return {"passes": len(self.pauses), "full_passes": len(full),
                "max_s": round(max((s for _, s in self.pauses), default=0.0),
                               4),
                "total_s": round(sum(s for _, s in self.pauses), 4)}


class Verdict:
    """The comparison that decides ``correct``, for whatever stands in the
    program's place: the answers it gave to the sampled tickets, the
    tickets it never answered and its tables after the window."""

    def __init__(self):
        self.wrong = 0
        self.checked = 0
        self.wrong_by: Dict[str, int] = {}

    def answer(self, template: str, got, want) -> None:
        self.checked += 1
        if differs(got, want):
            self.wrong += 1
            self.wrong_by[template] = self.wrong_by.get(template, 0) + 1

    def checks(self, missing: int, ref_tables, tables) -> Dict[str, Dict]:
        return {"answers_wrong": {"value": self.wrong, "limit": 0},
                "answers_missing": {"value": missing, "limit": 0},
                "table_cells_wrong": {
                    "value": table_cells_differing(ref_tables, tables),
                    "limit": 0}}


def is_correct(checks: Dict[str, Dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             devices, t_start: float, cstats: CompileStats,
             control: bool = False) -> Dict:
    """One run of the cell.  With ``control`` the same comparison also
    judges the control in the program's place: the plain reference with
    its reads one beat stale (``control.py``; the benchmark's own runs
    never do)."""
    import jax
    from jax.profiler import TraceAnnotation
    from browsers import ClosedLoop
    from tpcw_gen import WI_TIMEOUT, InteractionGenerator, generate_data
    cfg, traffic = cell.config, cell.traffic
    ss = np.random.SeedSequence(seed)
    data_ss, inter_ss, think_ss, sample_ss = ss.spawn(4)
    cs0 = cstats.snapshot()
    data = generate_data(np.random.default_rng(data_ss), cfg["items"],
                         cfg["customers"])
    eng = build_engine(cfg, data)
    gen = InteractionGenerator(np.random.default_rng(inter_ss),
                               traffic["mix"], cfg["items"],
                               cfg["customers"])
    think_rngs = [np.random.default_rng(s) for s in
                  think_ss.spawn(int(traffic["emulated_browsers"]))]
    sample = TicketSample(np.random.default_rng(sample_ss))
    loop = ClosedLoop(eng, gen, traffic, think_rngs, sample)
    warm_paths = warm_up(loop, gen, list(traffic["mix"]))
    jax.block_until_ready(eng.state)
    # what set-up left behind stays out of the collector's full passes
    gc.collect()
    gc_objects = len(gc.get_objects())
    gc.freeze()
    gcp = GcPauses()
    c0 = cstats.snapshot()
    t_open = time.perf_counter()
    setup_s = t_open - t_start
    t_close = t_open + seconds
    gcp.armed = True
    loop.open(t_open)
    tr = None
    if trace:
        trace_s = min(float(traffic["trace_s"]), seconds)
        t_trace = t_open + (seconds - trace_s) / 2
        loop.run_until(t_trace)
        tmp = tempfile.TemporaryDirectory(prefix="bench_trace_")
        jax.profiler.start_trace(tmp.name)
        with TraceAnnotation("trace_window"):
            loop.run_until(t_trace + trace_s)
        jax.profiler.stop_trace()
    loop.run_until(t_close)
    gcp.armed = False
    c1 = cstats.snapshot()
    loop.close(GRACE_S)
    t_drained = time.perf_counter()
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices[:cell.chips])
    read_back = {t: eng.snapshot(t) for t in cfg["tables"]}
    loop.engine = None
    del eng
    gc.unfreeze()
    gc.collect()
    if trace:
        from trace_reduce import reduce_dir
        tr = reduce_dir(tmp.name)
        tmp.cleanup()

    # ---- the reference's replay and the comparison --------------------
    t_ref = time.perf_counter()
    window = [r for r in loop.inters if t_open <= r.due < t_close]
    unanswered = sum(r.left for r in loop.inters if r.eb >= 0)
    picked = sample.by_beat()
    ref = Reference(cfg, data)
    stale = Reference(cfg, data) if control else None
    program, ctrl = Verdict(), Verdict()
    for b, beat in enumerate(loop.beats):
        ref.admit_writes(beat.writes)
        for t in picked.get(b, ()):
            want = ref.answer(t.template, t.params)
            program.answer(t.template, t.result, want)
            if stale is not None:
                ctrl.answer(t.template, as_output(
                    stale.answer(t.template, t.params)), want)
        if stale is not None:
            stale.admit_writes(beat.writes)
    checks = program.checks(unanswered, ref.tables, read_back)
    correct = is_correct(checks)
    ref_s = time.perf_counter() - t_ref

    in_window = [b for b in loop.beats
                 if b.stats is not None and t_open <= b.t_collected < t_close]
    gaps = np.diff([t_open] + [b.t_collected for b in in_window])
    view = RunView(
        seconds=seconds, setup_s=setup_s, t_open=t_open, t_close=t_close,
        inters=window, beats=in_window, config=cfg, traffic=traffic,
        trace=tr, device_kind=devices[0].device_kind)
    info = {
        "warm_up_paths": warm_paths,
        "compile": {"seconds": round(c0[0] - cs0[0], 3),
                    "cache_hits": c0[1] - cs0[1],
                    "cache_misses": c0[2] - cs0[2]},
        "window_compile_requests": (c1[1] - c0[1]) + (c1[2] - c0[2]),
        "window_backend_compiles": c1[3] - c0[3],
        "beats_in_window": len(in_window),
        "paths": _count(f"{b.stats['scan_path']}/{b.stats['join_path'] or '-'}"
                        for b in in_window),
        "longest_beat_gap_s": round(float(gaps.max()), 4) if gaps.size
        else None,
        "longest_collect_wait_s": round(max(
            (b.collect_s for b in in_window), default=0.0), 4),
        "gc_objects_at_open": gc_objects,
        "gc_in_window": gcp.close(),
        "held_over_writes": ref.held_over,
        "within_wi_limit": sum(1 for r in window if r.done is not None
                               and r.done - r.due <= WI_TIMEOUT[r.kind]),
        "drain_s": round(t_drained - t_close, 3),
        "answers_checked": program.checked,
        "wrong_by_template": program.wrong_by,
        "reference_s": round(ref_s, 3)}
    out = {"view": view, "checks": checks, "correct": correct,
           "attempted": len(window),
           "failed": sum(1 for r in window if r.done is None),
           "peak": peak, "info": info}
    if stale is not None:
        # the control answers every ticket; its tables are its own
        c_checks = ctrl.checks(0, ref.tables, {
            n: t.read_back() for n, t in stale.tables.items()})
        out["control"] = {"checks": c_checks, "correct": is_correct(c_checks),
                          "wrong_by_template": ctrl.wrong_by}
    return out


def as_output(answer: Dict) -> Dict:
    """A reference answer in the program's result layout (a group-by's
    top-k taken by stable descending sort)."""
    if "rows" in answer:
        return answer
    top = np.argsort(-answer["sums"], kind="stable")[:answer["top"]]
    return {"groups": top, "scores": answer["sums"][top],
            "counts": answer["counts"][top]}


def _count(items) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for x in items:
        out[x] = out.get(x, 0) + 1
    return out


def metrics_of(view, specs: List[Dict]) -> Dict[str, Dict]:
    out = {}
    for m in specs:
        value = load_reader(m["name"])(view)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise BenchError(f"no program under {SRC}: run from a checkout")
    if args.seed < 0:
        raise BenchError("--seed must be a whole number >= 0")
    cell = Cell(ROOT, args.workload)
    sys.path.insert(0, SRC)
    devices = init_jax(require_tpu=True, chips=cell.chips)
    res = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   devices, T_START, CompileStats())
    view = res["view"]
    specs = cell.per_layer if args.trace else cell.end_to_end
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics_of(view, specs),
            "device": {"platform": devices[0].platform,
                       "kind": devices[0].device_kind,
                       "count": len(devices),
                       "memory_peak_bytes": res["peak"]}}
    if args.trace:
        tr = view.trace
        line["device"]["busy_s"] = tr.busy_s
        line["device"]["window_s"] = tr.window_s
        line["breakdown"] = {"device_ops": tr.device_ops,
                             "idle_gaps": tr.idle_gaps}
    line["checks"] = res["checks"]
    log("run: " + json.dumps(res["info"]))
    for name, c in res["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BenchError as e:
        log(f"bench: {e}")
        sys.exit(2)
