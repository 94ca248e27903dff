"""``chip_smoke.py`` rehearsed on the CPU, and the import contract.

The chip run itself needs a TPU; these tests run the same phase
functions at a tiny size (the sharded one on 4 virtual CPU devices),
check that the script refuses to run without a TPU, and that importing
the library initializes no JAX backend (a process that holds the
backend would keep the chip from a child that needs it).
"""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.sparse import tuning

pytest.importorskip("scipy.sparse")

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(args, **env):
    full = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    full["PYTHONPATH"] = str(ROOT / "src") + os.pathsep + full.get(
        "PYTHONPATH", "")
    return subprocess.run([sys.executable, *args], env=full, cwd=ROOT,
                          capture_output=True, text=True, timeout=500)


@pytest.fixture
def tpu_plan_policy():
    """Resolve ``method=None`` to the TPU default ("radix", run here in
    interpret mode) for the duration of one test."""
    table = tuning.TuningTable()
    table.record("plan", {"method": tuning.prior_value("plan", "method",
                                                       "tpu")})
    tuning.set_table(table)
    yield
    tuning.reset_table()


@pytest.mark.parametrize("policy", ["cpu_default", "tpu_default"])
def test_single_chip_phases_tiny(policy, request):
    if policy == "tpu_default":
        request.getfixturevalue("tpu_plan_policy")
    lines = []
    _chip_smoke().single_chip_size((1, 2, 3), 0.002, log=lines.append)
    phases = [ln.split()[1] for ln in lines if ln.startswith("phase ")]
    for name in ("set1/multiply_AxA", "set3/spmv_symcsc",
                 "set2/update_structure_1pct", "set2/assemble_many2"):
        assert any(p.endswith(name) for p in phases), name


def test_sharded_phase_on_four_cpu_devices():
    code = (f"import sys; sys.path.insert(0, {str(ROOT)!r}); "
            "import chip_smoke; chip_smoke.sharded_phase(0.02, 4)")
    out = _run(["-c", code],
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert out.returncode == 0, out.stdout + out.stderr
    assert "sharded4/scale=0.02/set2/spmv" in out.stdout


def test_script_refuses_without_tpu():
    out = _run([str(SCRIPT)])
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_import_initializes_no_backend():
    # the dry-run asks for 512 host devices only when it runs, so a
    # process that imports it still starts its backend with one
    code = ("import repro.sparse, repro.kernels, repro.serve, "
            "repro.launch.dryrun; "
            "from jax._src import xla_bridge; "
            "assert not xla_bridge.backends_are_initialized(); "
            "import jax; assert len(jax.devices()) == 1")
    out = _run(["-c", code], XLA_FLAGS="")
    assert out.returncode == 0, out.stderr
