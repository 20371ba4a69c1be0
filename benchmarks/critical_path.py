"""Heartbeat critical-path microbenchmarks (the PR-2 perf record).

Three measurements, one per critical-path fix:

  join_scaling()      — partitioned bucketed probe vs the dense block
                        join at growing key counts (jnp backend, CPU);
                        the partitioned time INCLUDES the per-heartbeat
                        partition build, so the reported speedup is the
                        honest end-to-end ratio.
  dispatch_host_time()— packed single-transfer admission staging vs the
                        legacy per-template staging loop.  Both sides
                        time exactly reset + slot fill + H2D transfer
                        over preallocated buffers from the same admitted
                        batch, so the delta is purely the python scatter
                        loop + O(templates) transfers vs one packed
                        copy.  The full engine.dispatch() host time
                        (queue drain + staging + launch) rides along.
  cycle_times()       — mean heartbeat wall time over a TPC-W drain,
                        synchronous vs pipelined, via the executor's
                        per-cycle CycleResult accounting.

``python -m benchmarks.critical_path`` prints the dict; benchmarks/run.py
folds it into BENCH_PR3.json.
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import backends
from repro.core.executor import SharedDBEngine
from repro.core.lowering import partition_layout
from repro.core.storage import build_key_partitions
from repro.workloads import tpcw

SCALE = dict(scale_items=1000, scale_customers=2880)


def _best_of(fn, reps: int) -> float:
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def join_scaling(sizes=(512, 1024, 2048, 4096), W: int = 4,
                 reps: int = 5) -> List[Dict]:
    """Partitioned vs dense block join, Tl = Tr = keys, jnp backend."""
    be = backends.get_backend("jnp")
    out = []
    for T in sizes:
        rng = np.random.default_rng(T)
        keys_r = jnp.asarray(rng.permutation(T * 2)[:T], jnp.int32)
        keys_l = jnp.asarray(rng.choice(T * 2, T), jnp.int32)
        mask_l = jnp.asarray(rng.integers(0, 2**32, (T, W)), jnp.uint32)
        mask_r = jnp.asarray(rng.integers(0, 2**32, (T, W)), jnp.uint32)
        valid_r = jnp.asarray(rng.random(T) > 0.1)
        n_parts, bucket_cap = partition_layout(T)

        block = jax.jit(be.join_block)

        @jax.jit
        def partitioned(kl, ml, kr, mr, vr):
            parts = build_key_partitions(kr, vr, n_parts, bucket_cap)
            return be.join_partitioned(kl, ml, *parts, mr)

        args = (keys_l, mask_l, keys_r, mask_r, valid_r)
        jax.block_until_ready(block(*args))          # compile
        jax.block_until_ready(partitioned(*args))
        rb, mb = block(*args)
        rp, mp = partitioned(*args)
        assert (np.asarray(rb) == np.asarray(rp)).all()
        assert (np.asarray(mb) == np.asarray(mp)).all()
        t_block = _best_of(lambda: block(*args), reps)
        t_part = _best_of(lambda: partitioned(*args), reps)
        out.append({"keys": T, "n_partitions": n_parts,
                    "bucket_cap": bucket_cap,
                    "block_us": t_block * 1e6,
                    "partitioned_us": t_part * 1e6,
                    "speedup": t_block / max(t_part, 1e-12)})
    return out


def _legacy_stage(plan, bufs, tickets_by_tpl):
    """The pre-packed-ABI staging loop: per-template fill + per-template
    jnp.asarray — O(templates) H2D transfers per heartbeat."""
    batch = {}
    for name, tpl in plan.templates.items():
        params, active = bufs[name]
        active[:] = False
        admitted = tickets_by_tpl.get(name, ())[:len(active)]
        for slot, params_dict in enumerate(admitted):
            active[slot] = True
            for pi in range(len(tpl.preds)):
                params[slot, pi] = params_dict[pi]
        batch[name] = {"params": jnp.asarray(params),
                       "active": jnp.asarray(active)}
    return batch


def dispatch_host_time(n_queries: int = 64, reps: int = 30) -> Dict:
    """Host-side admission staging cost per heartbeat, packed vs legacy."""
    rng = np.random.default_rng(11)
    plan = tpcw.build_tpcw_plan(**SCALE)
    data = tpcw.generate_data(rng, **SCALE)
    gen = tpcw.WorkloadGenerator(rng, **SCALE)
    eng = SharedDBEngine(plan, tpcw.DEFAULT_UPDATE_SLOTS, data)
    eng.submit("get_book", {0: (1, 1)})
    eng.run_until_drained()                          # warm the jit cache

    queries = [q for it in gen.sample_mix("shopping", n_queries)
               for q in it.queries]
    tickets_by_tpl: Dict[str, list] = {}
    for name, params in queries:
        tickets_by_tpl.setdefault(name, []).append(params)
    # preallocated legacy buffers (parity with the packed path: neither
    # side pays allocation, the delta is loop + transfer count)
    legacy_bufs = {
        name: (np.zeros((plan.caps[name], max(len(t.preds), 1), 2),
                        np.int32),
               np.zeros((plan.caps[name],), bool))
        for name, t in plan.templates.items()}

    buf = eng._staging[0]

    def packed():
        # symmetric counterpart of _legacy_stage: reset + slot fill from
        # the same admitted batch + the single packed transfer pair
        buf.active[:] = False
        params, active = buf.params, buf.active
        for name, ps in tickets_by_tpl.items():
            tpl = plan.templates[name]
            off = plan.offsets[name]
            for slot, params_dict in enumerate(ps[:plan.caps[name]]):
                g = off + slot
                active[g] = True
                for pi in range(len(tpl.preds)):
                    params[g, pi] = params_dict[pi]
        return {"params": jnp.asarray(params),
                "active": jnp.asarray(active)}

    t_packed = _best_of(packed, reps)
    t_legacy = _best_of(
        lambda: _legacy_stage(plan, legacy_bufs, tickets_by_tpl), reps)

    # full dispatch() host time (staging + launch, returns pre-sync)
    def one_dispatch():
        for name, ps in tickets_by_tpl.items():
            for p in ps[:plan.caps[name]]:
                eng.submit(name, p)
        t0 = time.perf_counter()
        eng.dispatch()
        dt = time.perf_counter() - t0
        eng.collect()
        return dt

    one_dispatch()                                   # warm
    t_dispatch = min(one_dispatch() for _ in range(reps))
    return {"n_templates": len(plan.templates),
            "packed_stage_us": t_packed * 1e6,
            "per_template_stage_us": t_legacy * 1e6,
            "stage_speedup": t_legacy / max(t_packed, 1e-12),
            "dispatch_host_us": t_dispatch * 1e6}


def cycle_times(n_interactions: int = 120, reps: int = 3) -> Dict:
    """Mean heartbeat wall time, sync vs pipelined, over a TPC-W drain."""
    rng = np.random.default_rng(7)
    plan = tpcw.build_tpcw_plan(**SCALE)
    data = tpcw.generate_data(rng, **SCALE)
    gen = tpcw.WorkloadGenerator(rng, **SCALE)
    eng = SharedDBEngine(plan, tpcw.DEFAULT_UPDATE_SLOTS, data)
    eng.submit("get_book", {0: (1, 1)})
    eng.run_until_drained()                          # warm the jit cache

    means = {"sync": [], "pipelined": []}
    for _ in range(reps):
        for label, pipelined in (("sync", False), ("pipelined", True)):
            for it in gen.sample_mix("shopping", n_interactions):
                for q in it.queries:
                    eng.submit(*q)
                for u in it.updates:
                    eng.submit_update(*u)
            done = eng.run_until_drained(pipelined=pipelined)
            means[label].append(
                float(np.mean([d.wall_s for d in done])))
    sync = min(means["sync"])
    piped = min(means["pipelined"])
    return {"mean_cycle_us_sync": sync * 1e6,
            "mean_cycle_us_pipelined": piped * 1e6,
            "pipelined_sync_ratio": piped / max(sync, 1e-12)}


def run(smoke: bool = False) -> Dict:
    sizes = (1024, 4096) if smoke else (512, 1024, 2048, 4096)
    return {
        "join_scaling": join_scaling(sizes=sizes,
                                     reps=3 if smoke else 5),
        "dispatch": dispatch_host_time(reps=10 if smoke else 30),
        "cycle": cycle_times(n_interactions=30 if smoke else 120,
                             reps=1 if smoke else 3),
    }


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    import json
    print(json.dumps(run(smoke="--smoke" in __import__("sys").argv),
                     indent=2))
