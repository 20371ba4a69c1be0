"""Pipelined dispatch/collect vs the synchronous heartbeat loop.

Drains identical backlogs of TPC-W interactions through ONE compiled
engine, alternating between the synchronous ``run_cycle`` loop (dispatch
immediately followed by a blocking collect — the seed behaviour) and
``run_until_drained(pipelined=True)`` (up to ``pipeline_depth``
heartbeats in flight, so queue draining and numpy staging for cycle N+1
overlap device execution of cycle N).  Alternating reps on a shared
engine keep jit compilation and allocator state out of the comparison;
the minimum over reps is the noise-robust statistic.

    PYTHONPATH=src python benchmarks/pipeline_bench.py [n_interactions]
"""
from __future__ import annotations

import sys
import time

import numpy as np

from repro.core.executor import SharedDBEngine
from repro.workloads import tpcw

SCALE = dict(scale_items=1000, scale_customers=2880)


def run(n: int = 150, reps: int = 4, seed: int = 7):
    rng = np.random.default_rng(seed)
    plan = tpcw.build_tpcw_plan(**SCALE)
    data = tpcw.generate_data(rng, **SCALE)
    gen = tpcw.WorkloadGenerator(rng, **SCALE)

    engine = SharedDBEngine(plan, tpcw.DEFAULT_UPDATE_SLOTS, data)
    engine.submit("get_book", {0: (1, 1)})
    engine.run_until_drained()          # warm the jit cache

    times = {"sync": [], "pipelined": []}
    cycles = {"sync": 0, "pipelined": 0}
    for _ in range(reps):
        for label, pipelined in (("sync", False), ("pipelined", True)):
            inters = gen.sample_mix("shopping", n)
            tickets = []
            for it in inters:
                for q in it.queries:
                    tickets.append(engine.submit(*q))
                for u in it.updates:
                    engine.submit_update(*u)
            c0 = engine.cycles_run
            t0 = time.time()
            engine.run_until_drained(pipelined=pipelined)
            times[label].append(time.time() - t0)
            cycles[label] += engine.cycles_run - c0
            assert all(t.result is not None for t in tickets)

    rows = []
    for label in ("sync", "pipelined"):
        best = min(times[label])
        per_cycle = best / max(cycles[label] // reps, 1)
        rows.append((label, best, cycles[label] // reps, per_cycle))
        print(f"{label:9s}: min {best:6.3f}s/drain over {reps} reps, "
              f"~{cycles[label] // reps} cycles, "
              f"{per_cycle * 1e3:7.1f} ms/cycle", flush=True)
    sync, piped = rows[0][3], rows[1][3]
    print(f"pipelined/sync cycle-time ratio: {piped / sync:.3f} "
          f"(<= ~1.0 means the overlap does not regress latency)",
          flush=True)
    return rows


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(int(sys.argv[1]) if len(sys.argv) > 1 else 150)
