# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness: one entry per paper figure (Figs. 7-11) plus the
beyond-paper roofline report, the critical-path record, and the
incremental-scan / incremental-join / sharded-reseed records.

    python -m benchmarks.run [--quick]   # figures + BENCH_PR3/4/5.json
    python -m benchmarks.run --smoke     # machine-readable records only
                                         # (the CI cycle-time SLA gate);
                                         # refuses to overwrite committed
                                         # BENCH_PR*.json without --force

Every invocation (re)writes the machine-readable perf trajectory:
``BENCH_PR3.json`` (per-heartbeat cycle time, host dispatch/staging
time, the partitioned-vs-block join scaling curve, the pipelined/sync
cycle-time ratio, and the delta-vs-full-rescan scan curve +
steady-state heartbeat), ``BENCH_PR4.json`` (the delta-vs-full JOIN
probe curve + the index-less steady-state heartbeat) and
``BENCH_PR5.json`` (the sharded reseed beat on a multi-shard row mesh
vs a single shard — measured in a SUBPROCESS with forced host devices,
so the single-device records above stay undisturbed) and
``BENCH_PR6.json`` (the fused delta-heartbeat record: fused vs chained
steady-state beat with per-phase wall breakdown + launch counts, the
analytic fused-beat roofline footprint, and the end-to-end
sharded/single delta-beat ratio) and ``BENCH_PR8.json`` (the dynamic
plan-folding serving record: steady-state delta beat vs beats served
while a background fold builds the extended plan — gated within 1.5x —
plus the migration-beat wall and the post-fold fused steady beat).
``tests/test_sla_gate.py`` fails the build when any record regresses
past its stored thresholds — including when a record or row goes
missing.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

BENCH_JSON = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          os.pardir, "BENCH_PR3.json")
BENCH_PR4_JSON = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              os.pardir, "BENCH_PR4.json")
BENCH_PR5_JSON = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              os.pardir, "BENCH_PR5.json")
BENCH_PR6_JSON = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              os.pardir, "BENCH_PR6.json")
BENCH_PR8_JSON = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              os.pardir, "BENCH_PR8.json")


def write_bench_pr8(smoke: bool) -> dict:
    """The dynamic plan-folding serving record: steady-state delta beat
    wall vs beats served WHILE a background fold builds the extended
    plan (the gate holds the ratio within 1.5x — folding must not stall
    the world), plus the single migration-beat wall and the post-fold
    steady beat back on the fused single launch."""
    from benchmarks import fold_bench
    record = {"pr": 8, "mode": "smoke" if smoke else "full",
              "fold": fold_bench.run(smoke=smoke)}
    path = os.path.abspath(BENCH_PR8_JSON)
    with open(path, "w") as f:
        json.dump(record, f, indent=2)
        f.write("\n")
    fo = record["fold"]
    print(f"== Plan folding -> {path} ==", flush=True)
    print(f"steady delta beat {fo['steady_us']:.0f}us vs "
          f"{fo['during_fold_us']:.0f}us during the background fold "
          f"(ratio {fo['fold_serving_ratio']:.3f}; "
          f"{fo['beats_during_build']} beats served while the extended "
          f"plan built for {fo['build_wall_s']:.1f}s); migration beat "
          f"{fo['migration_beat_us']:.0f}us; post-fold steady "
          f"{fo['post_steady_us']:.0f}us "
          f"({fo['post_fold_launches']} launches)", flush=True)
    return record


def write_bench_pr6(smoke: bool, pr5_record: dict) -> dict:
    """The fused delta-heartbeat record: fused vs chained steady-state
    beat (single device, in-process like the PR-3/4 records) with the
    per-phase wall breakdown, per-beat backend-op launch counts and the
    analytic roofline footprint of one fused beat — plus the end-to-end
    sharded/single delta-beat ratio lifted from the PR-5 subprocess
    record (same forced-host mesh, so the ratio is apples-to-apples)."""
    from benchmarks import fused_bench
    e = pr5_record["sharded_engine"]
    record = {"pr": 6, "mode": "smoke" if smoke else "full",
              "fused": fused_bench.run(smoke=smoke),
              "sharded_delta": {
                  "shards": e["shards"],
                  "sharded_delta_heartbeat_us": e["delta_heartbeat_us"],
                  "single_delta_heartbeat_us":
                      e["single_delta_heartbeat_us"],
                  "ratio": e["sharded_delta_ratio"]}}
    path = os.path.abspath(BENCH_PR6_JSON)
    with open(path, "w") as f:
        json.dump(record, f, indent=2)
        f.write("\n")
    fu = record["fused"]
    print(f"== Fused delta heartbeat -> {path} ==", flush=True)
    print(f"fused {fu['fused']['wall_us']:.0f}us vs chained "
          f"{fu['chained']['wall_us']:.0f}us per delta beat "
          f"(ratio {fu['fused_vs_chained']:.3f}; fused launches "
          f"{fu['fused_launches']} vs chained "
          f"{fu['chained_launches']}); phase breakdown fused "
          f"stage/dispatch/kernel/collect = "
          f"{fu['fused']['stage_us']:.0f}/"
          f"{fu['fused']['dispatch_us']:.0f}/"
          f"{fu['fused']['kernel_us']:.0f}/"
          f"{fu['fused']['collect_us']:.0f}us; delta phase fused "
          f"{fu['delta_phase']['fused_us']:.0f}us vs chained "
          f"{fu['delta_phase']['chained_us']:.0f}us "
          f"({fu['delta_phase']['speedup']:.2f}x); sharded/single delta "
          f"ratio {record['sharded_delta']['ratio']:.2f}", flush=True)
    return record


def write_bench_pr5(smoke: bool) -> dict:
    """Run the sharded bench in a subprocess on 8 forced host CPU
    devices (set for the child before its jax initializes) and fold the
    record into ``BENCH_PR5.json``.  A failing subprocess fails the run
    — the SLA gate must never see a silently missing record.

    On an accelerator host this process already holds the chip, and a
    child that touches JAX could not reach it; the record is refused
    there instead of silently measuring the CPU."""
    import jax
    if jax.default_backend() != "cpu":
        raise SystemExit(
            "BENCH_PR5 is a forced-host-CPU record measured in a child "
            f"process; this process holds the {jax.default_backend()} "
            "device, so run `python -m benchmarks.sharded_bench` on its "
            "own instead")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    if "--xla_force_host_platform_device_count" not in \
            env.get("XLA_FLAGS", ""):
        env["XLA_FLAGS"] = " ".join(
            [env.get("XLA_FLAGS", ""),
             "--xla_force_host_platform_device_count=8"]).strip()
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), env.get("PYTHONPATH", "")]).rstrip(
        os.pathsep)
    cmd = [sys.executable, "-m", "benchmarks.sharded_bench"]
    if smoke:
        cmd.append("--smoke")
    out = subprocess.run(cmd, capture_output=True, text=True, cwd=root,
                         timeout=3600, env=env)
    if out.returncode != 0:
        raise RuntimeError(
            f"sharded bench failed:\n{out.stderr[-4000:]}")
    rec = json.loads(out.stdout)
    record = {"pr": 5, "mode": "smoke" if smoke else "full",
              "sharded_reseed": rec["per_device"],
              "sharded_engine": rec["engine"]}
    path = os.path.abspath(BENCH_PR5_JSON)
    with open(path, "w") as f:
        json.dump(record, f, indent=2)
        f.write("\n")
    rs, e = record["sharded_reseed"], record["sharded_engine"]
    print(f"== Sharded reseed -> {path} ==", flush=True)
    print(f"per-device reseed scan x{rs['shards']} shards: "
          f"{rs['shard_scan_us']:.0f}us vs single-shard "
          f"{rs['full_scan_us']:.0f}us ({rs['speedup']:.2f}x); "
          f"engine reseed sharded {e['sharded_reseed_us']:.0f}us vs "
          f"single {e['single_reseed_us']:.0f}us on forced host "
          f"devices; sharded delta beat {e['delta_heartbeat_us']:.0f}us "
          f"(delta fraction {e['delta_cycle_fraction']:.2f})",
          flush=True)
    return record


def _emit(name: str, us: float, derived: str):
    print(f"{name},{us:.1f},{derived}", flush=True)


def write_bench_json(smoke: bool) -> dict:
    from benchmarks import critical_path, delta_scan_bench
    record = {"pr": 3, "mode": "smoke" if smoke else "full",
              **critical_path.run(smoke=smoke),
              "delta_scan": delta_scan_bench.run(smoke=smoke)}
    path = os.path.abspath(BENCH_JSON)
    with open(path, "w") as f:
        json.dump(record, f, indent=2)
        f.write("\n")
    big = record["join_scaling"][-1]
    print(f"== Critical path -> {path} ==", flush=True)
    print(f"join {big['keys']}x{big['keys']}: partitioned "
          f"{big['partitioned_us']:.0f}us vs block {big['block_us']:.0f}us "
          f"({big['speedup']:.1f}x)", flush=True)
    print(f"staging: packed {record['dispatch']['packed_stage_us']:.0f}us "
          f"vs per-template "
          f"{record['dispatch']['per_template_stage_us']:.0f}us "
          f"({record['dispatch']['stage_speedup']:.1f}x)", flush=True)
    print(f"cycle: sync {record['cycle']['mean_cycle_us_sync']:.0f}us, "
          f"pipelined {record['cycle']['mean_cycle_us_pipelined']:.0f}us "
          f"(ratio {record['cycle']['pipelined_sync_ratio']:.3f})",
          flush=True)
    ds = record["delta_scan"]
    big = ds["curve"][-1]
    print(f"delta scan {big['rows']} rows: {big['delta_us']:.0f}us vs "
          f"full {big['full_us']:.0f}us ({big['speedup']:.1f}x); "
          f"steady heartbeat delta "
          f"{ds['heartbeat']['delta_heartbeat_us']:.0f}us vs full "
          f"{ds['heartbeat']['full_heartbeat_us']:.0f}us "
          f"(delta fraction "
          f"{ds['heartbeat']['delta_cycle_fraction']:.2f})", flush=True)

    from benchmarks import delta_join_bench
    record4 = {"pr": 4, "mode": "smoke" if smoke else "full",
               "delta_join": delta_join_bench.run(smoke=smoke)}
    path4 = os.path.abspath(BENCH_PR4_JSON)
    with open(path4, "w") as f:
        json.dump(record4, f, indent=2)
        f.write("\n")
    dj = record4["delta_join"]
    big = dj["curve"][-1]
    print(f"== Delta joins -> {path4} ==", flush=True)
    print(f"delta join {big['rows']} rows: {big['delta_us']:.0f}us vs "
          f"full probe {big['full_us']:.0f}us ({big['speedup']:.1f}x); "
          f"index-less steady heartbeat delta "
          f"{dj['heartbeat']['delta_heartbeat_us']:.0f}us vs full "
          f"{dj['heartbeat']['full_heartbeat_us']:.0f}us "
          f"(delta-join fraction "
          f"{dj['heartbeat']['delta_join_fraction']:.2f})", flush=True)

    record5 = write_bench_pr5(smoke)
    write_bench_pr6(smoke, record5)
    write_bench_pr8(smoke)
    return record


def _existing_bench_records():
    """Committed BENCH_PR*.json records a --smoke run would overwrite."""
    root = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        os.pardir)
    return sorted(
        os.path.abspath(os.path.join(root, f))
        for f in os.listdir(root)
        if f.startswith("BENCH_PR") and f.endswith(".json"))


def main() -> None:
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    quick = "--quick" in sys.argv
    t_start = time.time()

    if "--smoke" in sys.argv:
        # a smoke run writes the SAME BENCH_PR*.json paths as a full
        # run — silently clobbering committed full-mode records with
        # smoke-mode numbers poisons every later comparison.  Refuse
        # unless explicitly forced.
        existing = _existing_bench_records()
        if existing and "--force" not in sys.argv:
            print("refusing to overwrite committed bench records with "
                  "smoke-mode numbers:", file=sys.stderr)
            for p in existing:
                print(f"  {p}", file=sys.stderr)
            print("re-run with --force to overwrite them anyway",
                  file=sys.stderr)
            raise SystemExit(2)
        write_bench_json(smoke=True)
        print(f"total bench wall: {time.time() - t_start:.0f}s", flush=True)
        return

    from benchmarks import (fig7_throughput, fig8_scaling, fig9_interactions,
                            fig10_heavy_light, fig11_interaction,
                            roofline_report)

    print("== Fig 7: throughput vs load (3 mixes) ==", flush=True)
    rows = fig7_throughput.run(
        rates=(10, 60) if quick else (10, 40, 120, 250),
        duration=6.0 if quick else 10.0,
        mixes=("shopping",) if quick else ("browsing", "shopping",
                                           "ordering"))
    for mix, rate, rs, rb in rows:
        _emit(f"fig7_{mix}_r{rate}_shared", rs.mean_cycle_s * 1e6,
              f"good_wips={rs.good_wips:.2f};p99_s={rs.p99_s:.2f}")
        _emit(f"fig7_{mix}_r{rate}_qaat", 0.0,
              f"good_wips={rb.good_wips:.2f};p99_s={rb.p99_s:.2f}")

    print("== Fig 8: scaling with cores (projection) ==", flush=True)
    for k, sh, ba in fig8_scaling.run(n=24 if quick else 64):
        _emit(f"fig8_cores{k}", 0.0,
              f"shared_wips={sh:.1f};qaat_wips={ba:.1f}")

    print("== Fig 9: individual web interactions ==", flush=True)
    for kind, ws, wb in fig9_interactions.run(
            n_per_kind=8 if quick else 32):
        _emit(f"fig9_{kind}", 1e6 / max(ws, 1e-9),
              f"shared_wips={ws:.1f};qaat_wips={wb:.1f}")

    print("== Fig 10: heavy vs light batches ==", flush=True)
    for template, n, ts, tb in fig10_heavy_light.run(
            sizes=(1, 16, 64) if quick else (1, 4, 16, 64, 256)):
        _emit(f"fig10_{template}_n{n}", ts / max(n, 1) * 1e6,
              f"shared_s={ts:.3f};qaat_s={tb:.3f};"
              f"speedup={tb / max(ts, 1e-9):.2f}")

    print("== Fig 11: load interaction ==", flush=True)
    for hr, rs, rb in fig11_interaction.run(
            heavy_rates=(0, 20, 200) if quick else (0, 20, 80, 200, 400),
            duration=6.0 if quick else 12.0):
        _emit(f"fig11_heavy{hr}", rs.mean_cycle_s * 1e6,
              f"shared_good={rs.good_wips:.2f};qaat_good={rb.good_wips:.2f}")

    print("== Pipeline: dispatch/collect overlap vs sync ==", flush=True)
    from benchmarks import pipeline_bench
    for label, dt, cycles, per_cycle in pipeline_bench.run(
            n=100 if quick else 300):
        _emit(f"pipeline_{label}", per_cycle * 1e6,
              f"total_s={dt:.3f};cycles={cycles}")

    print("== Roofline (from dry-run artifacts) ==", flush=True)
    for arch, shape, r in roofline_report.run():
        _emit(f"roofline_{arch}_{shape}", r["step_time_s"] * 1e6,
              f"dom={r['dominant']};frac={r['roofline_fraction']:.3f}")

    write_bench_json(smoke=quick)

    print(f"total bench wall: {time.time() - t_start:.0f}s", flush=True)


if __name__ == "__main__":
    main()
