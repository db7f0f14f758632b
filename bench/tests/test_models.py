"""The harness takes a model as data. A configuration names its module,
bench/models/<model>.py, under the required key `model`, and everything
that belongs to one model comes from that module: a model other than
ResNet-50 joins the benchmark as new files and new BENCHMARK.json entries,
with no existing file of bench/ edited. Checked on a copy of the
benchmark to which such files are added; and the two readers that take
counts from the model read what they read before the model had a module
of its own."""
import hashlib
import importlib.util
import json
import os
import re
import shutil
import subprocess
import sys
import textwrap
import types
from pathlib import Path

import pytest

import run as bench_run
from peaks import peaks
from record import Run

ROOT = Path(__file__).resolve().parents[2]
BASE_CONFIG = "rn50-x15-1chip"

# A model of its own numbers, so a reader that read another model's would
# read other values. Its worker arguments end a run before any process
# starts: what the harness asked of it by then is in CALLS.
STUB = textwrap.dedent('''
    BUCKETS = (1, 3)
    PROGRAM = "jit_stub_forward"
    GAP_LIMIT = 0.5
    CALLS = []


    def size(config, rehearsal):
        CALLS.append("size")
        return {"tokens": 8 if rehearsal else config["tokens"]}


    def served(size):
        CALLS.append("served")
        return {"dtype": "int8", "batch_buckets": list(BUCKETS),
                "weights_bytes_per_copy": 1000 * size["tokens"]}


    def worker_args(size, rehearsal):
        CALLS.append("worker_args")
        raise RuntimeError(f"stub worker_args {size} {rehearsal}")


    def flop_per_request(size):
        CALLS.append("flop_per_request")
        return 1e9 * size["tokens"]


    def roofline_s(batch, peak, size):
        CALLS.append("roofline_s")
        return batch * size["tokens"] * 1e-6 * 1e12 / peak["bf16_flop_s"]
''')

# ResNet-50 under another name, with every call of its interface logged
# with the calling process: a whole run of a model that is new files only.
STUBNET = textwrap.dedent('''
    import importlib.util
    import os
    import sys
    from pathlib import Path

    HERE = Path(__file__).resolve().parent
    spec = importlib.util.spec_from_file_location("stubnet_base",
                                                  HERE / "resnet50.py")
    base = sys.modules[spec.name] = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(base)
    BUCKETS, PROGRAM, GAP_LIMIT = base.BUCKETS, base.PROGRAM, base.GAP_LIMIT


    def logged(name):
        fn = getattr(base, name)

        def call(*args):
            with open(HERE / "stubnet.calls", "a") as f:
                f.write(f"{os.getpid()} {name}\\n")
            return fn(*args)
        return call


    for name in ("size", "served", "worker_args", "compare",
                 "flop_per_request", "roofline_s", "control_gap"):
        globals()[name] = logged(name)
''')


def _digests(tree: Path) -> dict:
    return {str(p.relative_to(tree)): hashlib.sha256(p.read_bytes()).digest()
            for p in sorted(tree.rglob("*")) if p.is_file()}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """A checkout holding the benchmark, the program (linked) and, added
    as new files only, the stub models, their configurations and cells."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copy(ROOT / "BENCHMARK.json", root)
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (root / "src").symlink_to(ROOT / "src")
    before = _digests(root / "bench")

    bench = root / "bench"
    (bench / "models" / "stub.py").write_text(STUB)
    (bench / "models" / "stubnet.py").write_text(STUBNET)
    base = json.loads((bench / "configs" / f"{BASE_CONFIG}.json").read_text())
    stub = {k: base[k] for k in ("copies", "slo_ms", "clients", "controller")}
    stub.update(name="stub-1chip", model="stub", tokens=64, dtype="int8",
                batch_buckets=[1, 3], weights_bytes_per_copy=64000)
    configs = {
        "stub-1chip": stub,
        "stub-heavy": dict(stub, name="stub-heavy", weights_bytes_per_copy=1),
        "nomodel-1chip": {k: v for k, v in dict(
            stub, name="nomodel-1chip").items() if k != "model"},
        "ghost-1chip": dict(stub, name="ghost-1chip", model="ghost"),
        "upward-1chip": dict(stub, name="upward-1chip", model="../run"),
        "stubnet-x15": dict(base, name="stubnet-x15", model="stubnet"),
    }
    spec = json.loads((root / "BENCHMARK.json").read_text())
    for name, cfg in configs.items():
        (bench / "configs" / f"{name}.json").write_text(json.dumps(cfg))
        spec["configs"].append({"name": name, "source": "test",
                                "file": f"bench/configs/{name}.json",
                                "reduced": [], "why": "test"})
        spec["workloads"].append({"name": f"{name}.poisson80",
                                  "config": name, "traffic": "poisson80",
                                  "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    after = _digests(bench)
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == {
        "models/stub.py", "models/stubnet.py",
        *(f"configs/{n}.json" for n in configs)}
    return root


@pytest.fixture
def harness(copy, monkeypatch):
    """The copy's run.py, as a module of its own."""
    monkeypatch.setattr(sys, "path", list(sys.path))
    spec = importlib.util.spec_from_file_location(
        "copy_run", copy / "bench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_load_cell_resolves_the_stub(copy, harness):
    cell, config, model, traffic, e2e, per_layer = harness.load_cell(
        "stub-1chip.poisson80")
    assert Path(model.__file__) == copy / "bench" / "models" / "stub.py"
    assert config["model"] == "stub" and model.BUCKETS == (1, 3)
    assert traffic["arrivals"] == "poisson"
    assert {m["name"] for m in e2e} == {"goodput_rps", "setup_s"}


@pytest.mark.parametrize("config,named", [
    ("nomodel-1chip", "names no model"),
    ("ghost-1chip", "names model 'ghost'"),
    ("upward-1chip", "names no model"),
])
def test_a_configuration_without_a_model_module_is_refused(harness, config,
                                                           named):
    with pytest.raises(harness.RunError, match=named) as e:
        harness.load_cell(f"{config}.poisson80")
    assert config in str(e.value)


@pytest.mark.parametrize("rehearsal", [False, True])
def test_the_served_check_asks_the_stub(harness, rehearsal):
    # the configuration states what the stub serves, so the run goes on to
    # the stub's worker arguments, at the stub's own size
    size = {"tokens": 8 if rehearsal else 64}
    with pytest.raises(RuntimeError, match=re.escape(
            f"stub worker_args {size} {rehearsal}")):
        harness.run("stub-1chip.poisson80", 1, 1.0, False,
                    rehearsal=rehearsal)


def test_the_served_check_refuses_what_the_stub_does_not_serve(harness):
    with pytest.raises(harness.RunError,
                       match="states weights_bytes_per_copy 1;"
                             " the served path runs 64000"):
        harness.run("stub-heavy.poisson80", 1, 1.0, False)
    # a rehearsal's copies are smaller than the configuration states
    with pytest.raises(RuntimeError, match="stub worker_args"):
        harness.run("stub-heavy.poisson80", 1, 1.0, False, rehearsal=True)


def _execs(*batches, t0=2.0):
    return [types.SimpleNamespace(action_type="INFER", status="SUCCESS",
                                  t_start=t0 + 0.1 * i, batch_size=b)
            for i, b in enumerate(batches)]


def test_the_roofline_readers_read_the_stubs_counts(harness):
    model = harness.load_cell("stub-1chip.poisson80")[2]
    run = Run(seconds=10.0, chips=1, window=(0.0, 10.0),
              client={"latency_ok_s": [0.01] * 100},
              actions=_execs(1, 2, 3, 5) + _execs(3, t0=6.0), gauges={},
              model=model, size={"tokens": 64},
              peak={"bf16_flop_s": 1e12, "hbm_bytes_s": 1e9},
              trace={"programs_s": {"jit_stub_forward": 0.01,
                                    "jit_resnet50_forward": 5.0}},
              trace_window=(2.0, 5.0))
    # buckets 1, 3, 3, 3 in the traced window: 10 x 64 us over 10 ms
    assert harness.reader("fwd_roofline_pct")(run) == pytest.approx(6.4)
    # 100 requests x 64 GFLOP over 10 s of a 1 TFLOP/s chip
    assert harness.reader("mfu_pct")(run) == pytest.approx(64.0)
    assert model.CALLS == 4 * ["roofline_s"] + ["flop_per_request"]


# What the readers read on these runs before the harness took the model
# from the configuration (bench/metrics at commit 1ba2b94, with the counts
# then in bench/counts.py).
PINNED = [({"img": 224, "scale": 1, "num_classes": 1000}, 1,
           4.277747125544957, 5.124972552316751),
          ({"img": 64, "scale": 16, "num_classes": 1000}, 4,
           0.014144937728937728, 0.0010347240609137056)]


@pytest.mark.parametrize("size,chips,roofline,mfu", PINNED)
def test_resnet_readings_are_the_parents(size, chips, roofline, mfu):
    model = bench_run.load_model({"name": BASE_CONFIG, "model": "resnet50"})
    run = Run(seconds=10.0, chips=chips, window=(0.0, 10.0),
              client={"latency_ok_s": [0.01] * 12345},
              actions=(_execs(16, t0=1.0) + _execs(1, 3, 4, 7, 16, 9, 2)
                       + [types.SimpleNamespace(
                           action_type=kind, status=status, t_start=4.9,
                           batch_size=8) for kind, status in
                          (("INFER", "ERROR"), ("LOAD", "SUCCESS"))]
                       + _execs(16, t0=5.0) + _execs(1, t0=12.0)),
              gauges={}, model=model, size=size, peak=peaks("TPU v5 lite"),
              trace={"programs_s": {"jit_resnet50_forward": 0.05}},
              trace_window=(2.0, 5.0))
    assert bench_run.reader("fwd_roofline_pct")(run) == pytest.approx(
        roofline, rel=1e-12)
    assert bench_run.reader("mfu_pct")(run) == pytest.approx(mfu, rel=1e-12)


def test_the_harness_process_stays_off_jax():
    """The controller runs in the harness's process, whose heap its
    collections walk: loading a model and reading its counts imports no
    JAX there."""
    code = textwrap.dedent(f'''
        import sys
        sys.path[:0] = [{str(ROOT / "bench")!r}]
        import run
        _, config, model, *_ = run.load_cell("{BASE_CONFIG}.poisson80")
        size = model.size(config, False)
        model.served(size), model.worker_args(size, False)
        model.flop_per_request(size), model.roofline_s(16, {{
            "bf16_flop_s": 1.0, "hbm_bytes_s": 1.0}}, size)
        assert "jax" not in sys.modules, "jax imported"
    ''')
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


def test_a_model_of_new_files_runs_through_the_harness(copy, harness,
                                                       monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    log = copy / "bench" / "models" / "stubnet.calls"
    log.unlink(missing_ok=True)
    r = harness.run("stubnet-x15.poisson80", 2**31 + 31, 2.0, False,
                    rehearsal=True, rate=600.0)
    assert r["correct"], r["checks"]
    assert r["checks"]["rows_compared"]["value"] > 0
    calls: dict = {}
    for line in log.read_text().splitlines():
        pid, name = line.split()
        calls.setdefault(int(pid) == os.getpid(), set()).add(name)
    assert calls[True] == {"size", "served", "worker_args"}
    assert calls[False] == {"size", "compare"}      # the daemon's
