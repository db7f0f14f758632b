"""chip_smoke.py without a chip: it refuses to report a CPU run as a chip
run, and its CPU rehearsal drives every phase at reduced width."""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _env(tmp_path):
    return dict(os.environ,
                JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))


def test_chip_smoke_fails_without_a_chip_or_a_checkout(tmp_path):
    proc = subprocess.run([sys.executable, SMOKE], env=_env(tmp_path),
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and "device" not in last
    alone = tmp_path / "alone"
    alone.mkdir()
    (alone / "chip_smoke.py").write_text(open(SMOKE).read())
    proc = subprocess.run([sys.executable, str(alone / "chip_smoke.py")],
                          env=_env(tmp_path), capture_output=True,
                          text=True, timeout=120, cwd=alone)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_cpu_rehearsal_passes_every_phase_but_never_reports_ok(tmp_path):
    """Phase A (compile, logits against float32) and phase B (controller,
    jax daemon, loadgen under the 100 ms SLO) at 1/16 width on the CPU."""
    proc = subprocess.run([sys.executable, SMOKE, "--cpu-rehearsal"],
                          env=_env(tmp_path), capture_output=True,
                          text=True, timeout=600)
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and last["rehearsal"] is True
    assert last["failures"] == [], proc.stderr[-4000:]
    assert proc.returncode == 0
    assert "[phase A] logits vs float32 CPU reference" in proc.stdout
    assert "[phase B, 1 device(s)] sent" in proc.stdout
