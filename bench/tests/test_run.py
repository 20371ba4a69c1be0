"""The harness end to end at a tiny size on the CPU: it refuses to run
without a chip or without the program; a sound run comes out correct;
the control (the reference with reads one beat stale in the program's
place) and each fault planted under the timed path come out not correct.

The runs skip only the harness's look for a chip (``require_tpu=False``);
the engine then runs with the ``jnp`` kernels.
"""
import os
import shutil
import subprocess
import sys
import time

import pytest

import run
import tiny

ROOT = os.path.dirname(run.BENCH)
SEED = 2 ** 31 + 977


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "noidx-shopping-sat",
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


def _tiny_run(traffic="shopping-sat", control=False, seconds=2.0):
    devices = run.init_jax(require_tpu=False, chips=1)
    cell = tiny.tiny_cell(traffic=tiny.tiny_traffic(traffic))
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
