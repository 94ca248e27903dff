"""Coverage extras: dtype sweeps, spmv_t, serve loop, launcher surface."""
import subprocess
import sys
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import assemble_arrays, fsparse, spmv, spmv_t
from repro.core.oracle import dense_oracle
from repro.kernels import blocked_cumsum
from repro.kernels import spmv as spmv_kernel
from repro.kernels.spmv.ref import spmv_ell_ref


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_spmv_ell_dtypes(dtype):
    rng = np.random.default_rng(0)
    M, N, K = 96, 64, 8
    cols = jnp.asarray(rng.integers(0, N, (M, K)), jnp.int32)
    vals = jnp.asarray(rng.normal(size=(M, K)), dtype)
    x = jnp.asarray(rng.normal(size=N), dtype)
    y = spmv_kernel(cols, vals, x, block_r=32)
    yr = spmv_ell_ref(cols, vals, x)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(yr, np.float32),
        rtol=tol, atol=tol,
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_blocked_cumsum_dtypes(dtype):
    rng = np.random.default_rng(1)
    if dtype == jnp.int32:
        x = jnp.asarray(rng.integers(-9, 9, 777), dtype)
    else:
        x = jnp.asarray(rng.normal(size=777), dtype)
    c = blocked_cumsum(x, block_b=128)
    tol = 0 if dtype == jnp.int32 else (1e-5 if dtype == jnp.float32 else 0.25)
    np.testing.assert_allclose(
        np.asarray(c, np.float64), np.cumsum(np.asarray(x, np.float64)),
        rtol=tol, atol=tol * 100 if tol else 0,
    )


def test_spmv_t_matches_dense():
    rng = np.random.default_rng(2)
    ii = rng.integers(1, 41, 500)
    jj = rng.integers(1, 31, 500)
    ss = rng.normal(size=500)
    A = fsparse(ii, jj, ss, (40, 30))
    ref = dense_oracle(ii - 1, jj - 1, ss, 40, 30)
    y = jnp.asarray(rng.normal(size=40), jnp.float32)
    xt = spmv_t(A, y)
    np.testing.assert_allclose(
        np.asarray(xt), ref.T @ np.asarray(y), rtol=1e-4, atol=1e-4
    )


def test_nzmax_overflow_is_padded_not_corrupt():
    """nzmax smaller than nnz: extra uniques are dropped (capacity
    semantics), never corrupting the stored prefix."""
    rows = np.array([0, 1, 2, 3], np.int32)
    cols = np.array([0, 1, 2, 3], np.int32)
    vals = np.ones(4, np.float32)
    S = assemble_arrays(rows, cols, vals, M=4, N=4, nzmax=2)
    assert S.nzmax == 2
    # stored entries are a valid prefix of the true CSC
    assert np.asarray(S.indices).tolist() == [0, 1]


def _child_env():
    """Child env for launcher tests: no XLA_FLAGS of the pytest
    process (such as a forced host-device count) reaches the child."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    return env


@pytest.mark.slow  # model-level: subprocess serves a tiny model
def test_serve_launcher_end_to_end():
    env = _child_env()
    out = subprocess.run(
        [sys.executable, "-u", "-m", "repro.launch.serve", "--arch", "olmo_1b",
         "--reduced", "--batch", "2", "--prompt-len", "8", "--gen", "4",
         "--requests", "2"],
        env=env, capture_output=True, text=True, timeout=560,
    )
    assert out.returncode == 0, out.stderr[-500:]
    assert "tok/s" in out.stdout


@pytest.mark.slow  # model-level: subprocess trains a tiny model
def test_train_launcher_preemption_hook():
    """SIGTERM mid-training must checkpoint and exit 0."""
    import signal
    import tempfile
    import time
    env = _child_env()
    with tempfile.TemporaryDirectory() as d:
        logf = os.path.join(d, "out.log")
        with open(logf, "w") as lf:
            proc = subprocess.Popen(
                [sys.executable, "-u", "-m", "repro.launch.train", "--arch",
                 "olmo_1b", "--reduced", "--steps", "100000", "--batch",
                 "2", "--seq", "32", "--ckpt-dir", d, "--log-every", "10"],
                env=env, stdout=lf, stderr=subprocess.STDOUT, text=True,
            )
            # wait until the training LOOP is running (handler installed)
            for _ in range(120):
                time.sleep(1)
                if "step=10 " in open(logf).read() or                    "step=10\n" in open(logf).read() or                    "step=10" in open(logf).read():
                    break
            proc.send_signal(signal.SIGTERM)
            proc.wait(timeout=120)
        out = open(logf).read()
        assert proc.returncode == 0, out[-800:]
        assert "preempted" in out
        if "step=" in out:  # training had started -> state must be saved
            from repro.ckpt.checkpoint import CheckpointManager
            assert CheckpointManager(d).latest_step() is not None
