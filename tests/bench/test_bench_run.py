"""bench/run.py as a command: it refuses to start without a TPU and in a
checkout without the program, and prints no result then."""

import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ARGS = ["--workload", "mistral-7b.chat-steady", "--seed", "1",
        "--seconds", "1", "--trace", "0"]


def _run(root, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(root, "bench", "run.py"), *ARGS, *extra],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    lines = [ln for ln in out.strip().splitlines() if ln.strip()]
    if not lines:
        return True
    try:
        return "correct" not in json.loads(lines[-1])
    except (ValueError, TypeError):
        return True


def test_exits_nonzero_without_a_tpu():
    r = _run(ROOT)
    assert r.returncode not in (0, 3), r.stderr[-2000:]
    assert "no TPU" in r.stderr and _no_result(r.stdout)


def test_exits_nonzero_where_only_the_benchmark_is(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(os.path.join(ROOT, "tests", "bench"),
                    tmp_path / "tests" / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(str(tmp_path))
    assert r.returncode != 0 and _no_result(r.stdout)
    assert "no bigdl_tpu package" in r.stderr


def test_unknown_workload_is_an_error():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"), "--workload",
         "no-such.cell", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0 and _no_result(r.stdout)
    assert "no workload" in r.stderr


FAULTY_ENTRY = '''"""The engine entry with one problem reported after the drain (a test's)."""

from bench.entries import engine

KIND = engine.KIND


class Driver(engine.Driver):
    def finish(self):
        return super().finish() + ["injected by a test"]
'''


def test_a_problem_after_the_drain_prints_correct_false(tmp_path):
    """Part (c) of `correct`: a run whose engine reports a problem still ends
    with the contract's JSON line, and `correct` is false in it. The faulty
    engine is an entry file and a traffic file of the test's own."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "bigdl_tpu"), tmp_path / "bigdl_tpu")
    (tmp_path / "bench" / "entries" / "faulty.py").write_text(FAULTY_ENTRY)
    with open(os.path.join(ROOT, "bench", "traffic", "chat-closed.json")) as f:
        traffic = dict(json.load(f), entry="faulty")
    with open(tmp_path / "bench" / "traffic" / "chat-faulty.json", "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    cell = "mistral-7b.chat-faulty"
    b["workloads"].append({"name": cell, "config": "mistral-7b-int4",
                           "traffic": "chat-faulty", "chips": 1,
                           "why": "a test"})
    for m in b["end_to_end"]:
        if m["name"] == "itl_ms_p95":
            m["workloads"].append(cell)
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(b, f)
    r = subprocess.run(
        [sys.executable, os.path.join(root, "bench", "run.py"), "--workload",
         cell, "--seed", "2147483653", "--seconds", "2", "--trace", "0",
         "--rehearse"], cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert r.returncode == 3, r.stderr[-3000:]
    assert "PROBLEM: injected by a test" in r.stdout
    last = r.stdout.strip().splitlines()[-1]
    result = json.loads(last.split("not a result: ", 1)[1])
    assert result["correct"] is False
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert "itl_ms_p95" in result["metrics"]
