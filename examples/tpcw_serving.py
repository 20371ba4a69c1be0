"""End-to-end driver: the full TPC-W workload served by SharedDB.

Replays a stream of web interactions from the shopping mix against the
shared engine AND the query-at-a-time baseline, printing the throughput /
latency comparison (the in-miniature version of the paper's Fig. 7).

    PYTHONPATH=src python examples/tpcw_serving.py [n_interactions]
"""
import sys
import time

import numpy as np

from repro.core.baseline import QueryAtATimeEngine
from repro.core.executor import SharedDBEngine
from repro.runtime.compile_cache import enable_compile_cache
from repro.workloads import tpcw

enable_compile_cache()

n = int(sys.argv[1]) if len(sys.argv) > 1 else 150
rng = np.random.default_rng(1)
SCALE_I, SCALE_C = 1000, 2880

plan = tpcw.build_tpcw_plan(SCALE_I, SCALE_C)
data = tpcw.generate_data(rng, SCALE_I, SCALE_C)
shared = SharedDBEngine(plan, tpcw.DEFAULT_UPDATE_SLOTS, data)
qaat = QueryAtATimeEngine(plan, data)
gen = tpcw.WorkloadGenerator(rng, SCALE_I, SCALE_C)

inters = gen.sample_mix("shopping", n)
n_q = sum(len(it.queries) for it in inters)
n_u = sum(len(it.updates) for it in inters)
print(f"{n} shopping-mix interactions = {n_q} queries + {n_u} updates")

# ---- SharedDB: everything batched through the always-on plan -----------
t0 = time.time()
for it in inters:
    for q in it.queries:
        shared.submit(*q)
    for u in it.updates:
        shared.submit_update(*u)
shared.run_until_drained()
t_shared = time.time() - t0
print(f"SharedDB : {n / t_shared:7.1f} WIPS  "
      f"({shared.cycles_run} cycles, "
      f"{t_shared / max(shared.cycles_run, 1) * 1e3:.0f} ms/cycle, "
      f"includes first-cycle compile)")

# ---- query-at-a-time baseline ------------------------------------------
inters2 = gen.sample_mix("shopping", n)
t0 = time.time()
for it in inters2:
    for u in it.updates:
        qaat.apply_update(*u)
    for q in it.queries:
        qaat.execute(*q)
t_base = time.time() - t0
print(f"QueryAtAT: {n / t_base:7.1f} WIPS")
print(f"shared-vs-qaat wall ratio at n={n}: {t_base / t_shared:.2f}x "
      f"(grows with concurrency — see benchmarks/fig7, fig10, fig11)")
