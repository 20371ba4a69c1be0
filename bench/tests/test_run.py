"""The harness end to end at a tiny size on the CPU: it refuses to run
without a chip, without the program, or with a cell whose chips differ
from its configuration's row shards; it builds the engine on a row mesh
exactly where the configuration states shards; a sound run comes out
correct, on one device and on four; the control (the reference with
reads one beat stale in the program's place) and each fault planted
under the timed path come out not correct.

The runs skip only the harness's look for a chip (``require_tpu=False``);
the engine then runs with the ``jnp`` kernels.
"""
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

import run
import tiny

ROOT = os.path.dirname(run.BENCH)
SEED = 2 ** 31 + 977


def _cli(cwd, env_extra=None, workload="noidx-shopping-sat"):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    p = _cli(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(run.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_makes_its_cache_inside_a_fresh_checkout(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(run.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "src"), tmp_path / "src")
    env = {"JAX_COMPILATION_CACHE_DIR": ""}
    p = _cli(tmp_path, env)
    assert p.returncode != 0 and "no TPU" in p.stderr
    assert (tmp_path / ".jax_cache").is_dir()


def _checkout(tmp_path, bench):
    """A checkout in ``tmp_path`` with this ``BENCHMARK.json``."""
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    shutil.copytree(run.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "src"), tmp_path / "src")


@pytest.mark.parametrize("chips,shards", [(4, None), (1, 4)],
                         ids=["four_chips_one_shard", "one_chip_four_shards"])
def test_refuses_chips_other_than_the_configurations_shards(
        tmp_path, chips, shards):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = bench["workloads"][0]
    cell["chips"] = chips
    _checkout(tmp_path, bench)
    if shards is not None:
        conf = next(c["file"] for c in bench["configs"]
                    if c["name"] == cell["config"])
        cfg = json.loads((tmp_path / conf).read_text())
        cfg["shards"] = shards
        (tmp_path / conf).write_text(json.dumps(cfg))
    with pytest.raises(run.BenchError, match="shard"):
        run.Cell(str(tmp_path), cell["name"])
    # before any device work: exit 2, no result line
    p = _cli(tmp_path, workload=cell["name"])
    assert p.returncode == 2, p.stderr
    assert p.stdout.strip() == ""
    assert "row shard" in p.stderr and "no TPU" not in p.stderr


@pytest.mark.parametrize("shards", [1, 4])
def test_build_engine_mesh_follows_the_configurations_shards(
        monkeypatch, shards):
    import repro.core.executor as executor
    from tpcw_gen import generate_data
    calls = []
    monkeypatch.setattr(executor, "SharedDBEngine",
                        lambda *a, **kw: calls.append(kw))
    cfg = tiny.tiny_config()
    if shards > 1:
        cfg["shards"] = shards
    run.build_engine(cfg, generate_data(np.random.default_rng(1),
                                        cfg["items"], cfg["customers"]))
    (kw,) = calls
    mesh = kw.pop("mesh")
    assert kw == {"jit": True, "kernels": "auto", "pipeline_depth": 2}
    if shards == 1:
        assert mesh is None
    else:
        assert dict(mesh.shape) == {"row": shards}


def _tiny_run(traffic="shopping-sat", control=False, seconds=2.0,
              cell=None):
    cell = cell or tiny.tiny_cell(traffic=tiny.tiny_traffic(traffic))
    devices = run.init_jax(require_tpu=False, chips=cell.chips)
    return run.run_cell(cell, SEED, seconds, False, devices,
                        time.perf_counter(), run.CompileStats(),
                        control=control)


def test_sound_run_is_correct_and_control_is_not():
    res = _tiny_run(control=True)
    assert res["correct"], res["checks"]
    assert res["info"]["answers_checked"] > 100
    assert res["attempted"] > 0 and res["failed"] == 0
    # the same comparison, with the control in the program's place
    ctrl = res["control"]
    assert ctrl["correct"] is False, ctrl["checks"]
    assert ctrl["checks"]["answers_wrong"]["value"] > 0


def test_sharded_run_is_correct_and_control_is_not(monkeypatch):
    built = []
    build = run.build_engine
    monkeypatch.setattr(run, "build_engine",
                        lambda cfg, data: built.append(build(cfg, data))
                        or built[-1])
    cfg = tiny.tiny_config()
    cfg["shards"] = 4
    cell = tiny.tiny_cell(config=cfg)
    cell.chips = 4
    res = _tiny_run(control=True, cell=cell)
    assert res["correct"], res["checks"]
    assert res["info"]["answers_checked"] > 100
    assert res["attempted"] > 0 and res["failed"] == 0
    assert len(res["info"]["peak_bytes_by_device"]) == 4
    assert res["control"]["correct"] is False, res["control"]["checks"]
    # the spine is row-sharded over the four devices
    (eng,) = built
    table = eng.state["order_line"]
    for col in cfg["tables"]["order_line"]["columns"] + ["_valid"]:
        assert len(table[col].sharding.device_set) == 4, col
        assert not table[col].sharding.is_fully_replicated, col
    # each heartbeat flavour the run reached has its compiled memory
    mem = run.beat_memory(eng)
    assert set(mem) == {"jit_beat_full", "jit_beat_delta",
                        "jit_beat_delta_join"}
    assert all(m["argument"] > 0 and m["output"] > 0 for m in mem.values())


def _drop_writes(monkeypatch):
    """A beat that returns its tables unchanged."""
    import repro.core.storage as storage
    monkeypatch.setattr(storage, "apply_updates",
                        lambda schema, table, batch, commit_cap=None: table)


def _half_batch(monkeypatch):
    """Every beat answers only half of the queries it admitted."""
    from repro.core.executor import SharedDBEngine
    orig = SharedDBEngine._collect_oldest

    def half(self):
        return {n: ts[::2] for n, ts in orig(self).items()}
    monkeypatch.setattr(SharedDBEngine, "_collect_oldest", half)


def _alter_answer(monkeypatch):
    """One row id altered where the answer is produced."""
    from repro.core.executor import SharedDBEngine
    orig = SharedDBEngine._collect_oldest

    def altered(self):
        out = orig(self)
        for t in out.get("get_related", [])[:1]:
            rows = t.result["rows"].copy()
            rows[0] += 1
            t.result = dict(t.result, rows=rows)
        return out
    monkeypatch.setattr(SharedDBEngine, "_collect_oldest", altered)


@pytest.mark.parametrize("fault", [_drop_writes, _half_batch, _alter_answer],
                         ids=["state_unchanged", "half_batch",
                              "answer_altered"])
def test_fault_under_the_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    res = _tiny_run()
    assert not res["correct"], res["checks"]
