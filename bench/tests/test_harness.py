"""Whole runs of the harness on the CPU at 1/16 width: the look for a chip
is skipped (`rehearsal`), everything else runs as on the chip. A sound
run comes out correct; with the timed path broken underneath, in the
daemon's EXEC where the answer is produced, it does not."""
import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

import run as bench_run

ROOT = Path(__file__).resolve().parents[2]
CELL = "rn50-x15-1chip.poisson80"
# at 600 r/s on 15 copies the CPU daemon batches up to bucket 4
RATE = 600.0

# Loaded by every child through PYTHONPATH; only the daemon acts on it.
FAULTS = textwrap.dedent('''
    import os, sys
    if sys.argv and sys.argv[0].endswith("daemon.py"):
        import signal
        from repro.core.worker import NotLoadedError
        from repro.serving import engine
        fault = os.environ["BENCH_TEST_FAULT"]
        execute = engine.JaxModel.execute
        window = []                        # set once the window opens
        set_handler = signal.signal

        def chained(sig, handler):
            if sig == signal.SIGUSR1 and callable(handler):
                def opened(*a):
                    window.append(True)
                    handler(*a)
                return set_handler(sig, opened)
            return set_handler(sig, handler)

        signal.signal = chained

        def broken(model, b, x, device):
            out = execute(model, b, x, device)
            if fault == "answer":          # one logit of one row altered
                return out.at[0, 0].add(abs(out[0]).max())
            if fault == "half_batch" and b > 1:   # rows past half left out
                return out.at[b // 2:].set(out[:b - b // 2])
            if fault == "no_answer" and window:   # the window's EXECs fail
                raise NotLoadedError(model.model_id)
            return out

        engine.JaxModel.execute = broken
''')


@pytest.fixture
def cpu_env(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")


@pytest.fixture
def fault(tmp_path, monkeypatch, request):
    (tmp_path / "sitecustomize.py").write_text(FAULTS)
    monkeypatch.setenv("PYTHONPATH", os.pathsep.join(
        filter(None, [str(tmp_path), os.environ.get("PYTHONPATH")])))
    monkeypatch.setenv("BENCH_TEST_FAULT", request.param)
    return request.param


def test_sound_run_is_correct(cpu_env):
    r = bench_run.run(CELL, 2**31 + 21, 2.0, False, rehearsal=True,
                      rate=RATE)
    assert r["correct"], r["checks"]
    assert r["attempted"] == 2 * RATE
    assert r["failed"] == 0
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(r["metrics"]) == {m["name"] for m in spec["end_to_end"]}
    assert max(r["diag"]["buckets_run"]) > 1


@pytest.mark.parametrize("fault", ["answer", "half_batch"], indirect=True)
def test_broken_exec_is_not_correct(cpu_env, fault):
    r = bench_run.run(CELL, 2**31 + 22, 2.0, False, rehearsal=True,
                      rate=RATE)
    assert not r["correct"]
    assert r["checks"]["logit_gap"]["value"] > \
        r["checks"]["logit_gap"]["limit"]


@pytest.mark.parametrize("fault", ["no_answer"], indirect=True)
def test_run_that_served_nothing_is_not_correct(cpu_env, fault):
    r = bench_run.run(CELL, 2**31 + 24, 2.0, False, rehearsal=True,
                      rate=RATE)
    assert not r["correct"]
    assert r["checks"]["rows_compared"]["value"] == 0


def test_no_tpu_prints_no_result(cpu_env):
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 23), "--seconds", "1", "--trace", "0"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=600)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_outside_a_checkout_prints_no_result(tmp_path, cpu_env):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL, "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=120)
    assert p.returncode != 0 and p.stdout == ""


def test_benchmark_file_names_existing_files():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        assert (ROOT / c["file"]).is_file()
    for w in spec["workloads"]:
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
    for m in spec["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
