"""The harness on the CPU at tiny sizes: discovery by name of files
added in a temporary directory (loops among them), a whole run without
the chip, on one device and on four, the bfloat16 control and planted
faults failing the check, and the refusal to run without a TPU."""
import io
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, loops  # noqa: E402

BENCH = ROOT / "bench"
DATA = BENCH / "tests" / "data"

TINY_CONFIG = {
    "name": "tiny41", "generator": "tiny_gen",
    "siz": 300, "nnz_row": 8, "nrep": 2, "L": 4800, "M": 300, "N": 300,
    "reduced": [], "limits": {"structure_mismatches": 0,
                              "data_rel_err": 1e-4},
}


@pytest.fixture(autouse=True)
def _restore_compile_cache():
    """A run points JAX's persistent compile cache into its checkout;
    put the process-wide settings back for the tests that follow."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    flags = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {f: getattr(jax.config, f) for f in flags}
    yield
    for flag, value in saved.items():
        jax.config.update(flag, value)
    compilation_cache.reset_cache()


@pytest.fixture()
def tiny_root(tmp_path):
    """A checkout that holds a configuration, a traffic mix, a generator
    and a metric that the harness has never seen, each in a file of its
    own, and a ``BENCHMARK.json`` that names them."""
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "metrics", "generators"):
        (bench / sub).mkdir(parents=True)
    (bench / "configs" / "tiny41.json").write_text(json.dumps(TINY_CONFIG))
    shutil.copy(BENCH / "generators" / "table41.py",
                bench / "generators" / "tiny_gen.py")
    (bench / "traffic" / "refill_small.json").write_text(json.dumps(
        {"loop": "refill", "value_sets": 3, "check_sample": 2}))
    (bench / "traffic" / "new_small.json").write_text(json.dumps(
        {"loop": "new", "value_sets": 2, "queue_depth": 2,
         "check_sample": 3}))
    for name in ("setup_s", "fill_rate", "new_rate"):
        shutil.copy(BENCH / "metrics" / f"{name}.py",
                    bench / "metrics" / f"{name}.py")
    (bench / "metrics" / "requests_done.py").write_text(
        "def read(ctx):\n    return len(ctx.latencies)\n")
    spec = {
        "configs": [{"name": "tiny41", "file": "bench/configs/tiny41.json"}],
        "workloads": [
            {"name": "tiny41.refill_small", "config": "tiny41",
             "traffic": "refill_small", "chips": 1},
            {"name": "tiny41.new_small", "config": "tiny41",
             "traffic": "new_small", "chips": 1}],
        "end_to_end": [
            {"name": "fill_rate", "unit": "Mtriplets/s",
             "workloads": ["tiny41.refill_small"]},
            {"name": "new_rate", "unit": "Mtriplets/s",
             "workloads": ["tiny41.new_small"]},
            {"name": "requests_done", "unit": "requests"},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": [],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp_path


def _run(root, workload, seed=2**31 + 7, **kw):
    from repro.sparse import plan_cache_clear

    plan_cache_clear()
    out, err = io.StringIO(), io.StringIO()
    try:
        rc = harness.run(root, workload, seed, 0.2, False,
                         bench=root / "bench", require_tpu=False, out=out,
                         err=err, **kw)
    finally:   # a planted fault must not leave plans behind
        plan_cache_clear()
    return rc, out.getvalue(), err.getvalue()


def test_files_added_by_name_are_found(tiny_root):
    spec = harness.load_spec(tiny_root)
    cell = harness.find_cell(spec, "tiny41.refill_small")
    assert harness.load_config(tiny_root, spec, cell["config"]) == TINY_CONFIG
    bench = tiny_root / "bench"
    assert harness.load_traffic(bench, "refill_small")["loop"] == "refill"
    assert harness.load_generator(bench, "tiny_gen").generate(
        TINY_CONFIG, 1)[0].size == 4800
    names = [m["name"] for m in
             harness.cell_metrics(spec, "tiny41.refill_small", False)]
    assert names == ["fill_rate", "requests_done", "setup_s"]
    assert harness.load_reader(bench, "requests_done").read(
        harness.Context(cfg={}, setup_s=0, latencies=[1, 2],
                        triplets=0, window_s=1, trace=None, peak={})) == 2


@pytest.mark.parametrize("workload,rate", [
    ("tiny41.refill_small", "fill_rate"),
    ("tiny41.new_small", "new_rate"),
])
def test_whole_run_without_the_chip(tiny_root, workload, rate):
    rc, out, err = _run(tiny_root, workload)
    assert rc == 0, err
    result = json.loads(out.strip().splitlines()[-1])
    assert result["correct"] is True, err
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {rate, "requests_done", "setup_s"}
    assert result["metrics"]["requests_done"]["value"] == result["attempted"]
    assert list(result)[-1] == "checks"
    assert result["checks"]["structure_mismatches"]["value"] == 0
    assert err.strip().splitlines()[-1].startswith("check correct=True")


def test_declared_metric_that_reads_nothing_fails(tiny_root):
    (tiny_root / "bench" / "metrics" / "requests_done.py").write_text(
        "NAMES = ('jit_renamed',)\n\n\ndef read(ctx):\n    return None\n")
    rc, out, err = _run(tiny_root, "tiny41.refill_small")
    assert rc != 0 and out == ""
    assert "requests_done" in err and "jit_renamed" in err


@pytest.mark.parametrize("workload", ["tiny41.refill_small",
                                      "tiny41.new_small"])
def test_bf16_control_is_not_correct(tiny_root, workload):
    rc, out, err = _run(tiny_root, workload, control=True)
    result = json.loads(out.strip().splitlines()[-1])
    assert result["correct"] is False
    c = result["checks"]["data_rel_err"]
    assert c["value"] > 10 * c["limit"]


def _alter_value(monkeypatch):
    from repro.sparse import pattern

    scatter = pattern.SparsePattern.scatter

    def altered(self, vals, **kw):
        return scatter(self, vals, **kw).at[0].multiply(1.001)

    monkeypatch.setattr(pattern.SparsePattern, "scatter", altered)


def _alter_structure(monkeypatch):
    import dataclasses

    from repro.sparse import pattern

    plan = pattern.plan

    def altered(*a, **kw):
        pat = plan(*a, **kw)
        return dataclasses.replace(pat, indices=pat.indices.at[0].add(1))

    monkeypatch.setattr(pattern, "plan", altered)


def _one_behind(fn):
    """``fn`` answering every call but the first with the result of the
    call before it."""
    done = []

    def stale(*a, **kw):
        done.append(fn(*a, **kw))
        return done.pop(0) if len(done) > 1 else done[0]

    return stale


def _stale_result(monkeypatch):
    import repro.sparse as sparse

    monkeypatch.setattr(sparse.PlanService, "assemble",
                        _one_behind(sparse.PlanService.assemble))
    monkeypatch.setattr(sparse, "fsparse", _one_behind(sparse.fsparse))


@pytest.mark.parametrize("fault,number", [
    (_alter_value, "data_rel_err"),
    (_alter_structure, "structure_mismatches"),
    (_stale_result, "data_rel_err"),
])
@pytest.mark.parametrize("workload", ["tiny41.refill_small",
                                      "tiny41.new_small"])
def test_planted_fault_is_not_correct(tiny_root, monkeypatch, workload,
                                      fault, number):
    fault(monkeypatch)
    rc, out, err = _run(tiny_root, workload)
    result = json.loads(out.strip().splitlines()[-1])
    assert result["correct"] is False
    c = result["checks"][number]
    assert c["value"] > c["limit"]


def _add_cell(root, traffic: str, mix: dict, chips: int = 1) -> str:
    """Add the traffic mix ``traffic`` and a cell of it on ``tiny41``
    to a checkout; returns the cell's name."""
    (root / "bench" / "traffic" / f"{traffic}.json").write_text(
        json.dumps(mix))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    name = f"tiny41.{traffic}"
    spec["workloads"].append({"name": name, "config": "tiny41",
                              "traffic": traffic, "chips": chips})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return name


NAMED_LOOP = """from bench import loops


class NamedRefill(loops.RefillLoop):
    \"\"\"``refill``, found by the name of its file.\"\"\"


LOOP = NamedRefill
"""


def test_loop_file_found_by_name_runs(tiny_root):
    (tiny_root / "bench" / "traffic" / "refill_named.py").write_text(
        NAMED_LOOP)
    cell = _add_cell(tiny_root, "refill_named_small",
                     {"loop": "refill_named", "value_sets": 2,
                      "check_sample": 2})
    loop = harness.load_loop(tiny_root / "bench", "refill_named")
    assert loop.__name__ == "NamedRefill"
    assert issubclass(loop, loops.RefillLoop)
    rc, out, err = _run(tiny_root, cell)
    assert rc == 0, err
    assert "loop NamedRefill" in err
    result = json.loads(out.strip().splitlines()[-1])
    assert result["correct"] is True, err
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert result["device"]["memory_peak_bytes_by_device"] == [
        result["device"]["memory_peak_bytes"]]


@pytest.mark.parametrize("source", [None, "LOOPS = {}\n"])
def test_loop_not_found_fails_with_the_path(tiny_root, source):
    path = tiny_root / "bench" / "traffic" / "no_such_loop.py"
    if source is not None:   # a file that defines no LOOP
        path.write_text(source)
    cell = _add_cell(tiny_root, "broken", {"loop": "no_such_loop"})
    rc, out, err = _run(tiny_root, cell)
    assert rc != 0 and out == ""
    assert str(path) in err


@pytest.mark.parametrize("name,cls", [("refill", loops.RefillLoop),
                                      ("new", loops.NewLoop)])
def test_builtin_loops_resolve_as_before(name, cls):
    assert harness.load_loop(BENCH, name) is cls
    assert harness.load_loop(BENCH, name) is loops.LOOPS[name]


SHARDED_RUN = """
import io, json, sys
from pathlib import Path
sys.path[:0] = [{root!r}, {src!r}]
from bench import harness

out = []
for control in (False, True):
    o, e = io.StringIO(), io.StringIO()
    rc = harness.run(Path({tiny!r}), {cell!r}, 2**31 + 11, 0.2, False,
                     bench=Path({tiny!r}) / "bench", require_tpu=False,
                     control=control, out=o, err=e)
    assert rc == 0, e.getvalue()
    out.append(json.loads(o.getvalue().strip().splitlines()[-1]))
    out[-1]["stderr"] = e.getvalue()
print(json.dumps(out))
"""


def test_loop_file_drives_the_sharded_path_on_four_devices(tiny_root):
    """A cell's own loop over ``PlanService(method="sharded")`` on four
    host devices: the block-row result is rebuilt as one CSC after the
    window and checked, and every device's memory is read."""
    shutil.copy(DATA / "sharded_refill.py",
                tiny_root / "bench" / "traffic" / "sharded_refill.py")
    cell = _add_cell(tiny_root, "sharded_small",
                     {"loop": "sharded_refill", "value_sets": 2,
                      "check_sample": 2}, chips=4)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = SHARDED_RUN.format(root=str(ROOT), src=str(ROOT / "src"),
                              tiny=str(tiny_root), cell=cell)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    run, control = json.loads(p.stdout.strip().splitlines()[-1])
    assert "loop ShardedRefillLoop on 4 of 4" in run["stderr"]
    assert run["correct"] is True, run["stderr"]
    assert run["failed"] == 0 and run["attempted"] >= 1
    assert run["checks"]["structure_mismatches"]["value"] == 0
    assert len(run["device"]["memory_peak_bytes_by_device"]) == 4
    assert run["device"]["memory_peak_bytes"] == max(
        run["device"]["memory_peak_bytes_by_device"])
    assert control["correct"] is False
    c = control["checks"]["data_rel_err"]
    assert c["value"] > 10 * c["limit"]


def test_no_tpu_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "table41_set2.refill", "--seed", "5", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
