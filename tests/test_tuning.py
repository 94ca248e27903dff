"""The execution-policy layer: registry, table, persistence, consumers.

Covers the PR-10 contracts: resolution falls back to the former
compile-time constants (priors), measured entries overlay them by
specificity, tables round-trip through JSON next to the plan caches
(corrupt files degrade with ``CacheCorruptionWarning``), the
``REPRO_TUNE`` / ``REPRO_TUNING_CACHE_DIR`` environment knobs work,
the deprecated residency-cap aliases can never diverge from the
registry budget, dispatch consults the table, resolved policies are
bit-identical to explicit priors, and the analysis-layer validator +
constant lint hold the single-home invariant.
"""
from __future__ import annotations

import json
import warnings

import jax
import numpy as np
import pytest

from repro.sparse import dispatch, serving
from repro.sparse import tuning
from repro.sparse.analysis import (
    lint_tuning_constants,
    validate_tuning_table,
)
from repro.sparse.errors import CacheCorruptionWarning, InvariantViolation


@pytest.fixture(autouse=True)
def _fresh_table():
    """Each test gets an empty process-global table (and leaves none)."""
    tuning.set_table(tuning.TuningTable())
    yield
    tuning.reset_table()


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------
def test_registered_families_cover_all_kernel_layers():
    fams = tuning.registered_families()
    for fam in ("plan", "merge", "radix_sort", "segment_sum", "spmv",
                "spmv_sym", "counting_sort"):
        assert fam in fams


def test_unknown_family_and_knob_raise():
    with pytest.raises(KeyError, match="unknown kernel family"):
        tuning.kernel_spec("nope")
    with pytest.raises(KeyError, match="no knob"):
        tuning.kernel_spec("spmv").knob("warp_size")


def test_priors_are_backend_aware():
    assert tuning.prior_policy("plan", "tpu")["method"] == "radix"
    assert tuning.prior_policy("plan", "cpu")["method"] == "fused"
    # the Pallas merge and spmv_sym kernels do not compile for the TPU,
    # so their priors name the XLA path on every backend
    for backend in ("tpu", "cpu"):
        assert tuning.prior_value("merge", "method", backend) == "jnp"
        assert tuning.prior_value("spmv_sym", "method", backend) == "ref"


def test_every_resident_budget_prior_is_the_registry_budget():
    for fam in ("merge", "segment_sum", "spmv_sym"):
        assert (
            tuning.prior_value(fam, "resident_max_bytes")
            == tuning.RESIDENT_BUDGET_BYTES
        )


# ---------------------------------------------------------------------------
# Resolution
# ---------------------------------------------------------------------------
def test_resolve_without_entries_returns_priors():
    assert tuning.resolve_policy(
        "radix_sort", backend="cpu"
    ) == tuning.prior_policy("radix_sort", "cpu")


def test_measured_entry_overrides_prior_by_bucket():
    t = tuning.get_table()
    t.record("radix_sort", {"block_b": 16384}, backend="cpu", L=100_000)
    pol = tuning.resolve_policy("radix_sort", backend="cpu", L=120_000)
    assert pol["block_b"] == 16384
    # same power-of-two bucket -> applies; different bucket -> priors
    far = tuning.resolve_policy("radix_sort", backend="cpu", L=100)
    assert far["block_b"] == tuning.prior_value("radix_sort", "block_b")
    # other knobs keep their priors
    assert pol["max_bits"] == tuning.prior_value("radix_sort", "max_bits")


def test_more_specific_entry_wins():
    t = tuning.get_table()
    t.record("spmv", {"block_r": 128}, backend="cpu")
    t.record("spmv", {"block_r": 512}, backend="cpu", L=1 << 20)
    assert tuning.resolve_policy(
        "spmv", backend="cpu", L=1 << 20
    )["block_r"] == 512
    assert tuning.resolve_policy(
        "spmv", backend="cpu", L=8
    )["block_r"] == 128


def test_measured_false_and_env_disable_return_priors(monkeypatch):
    t = tuning.get_table()
    t.record("spmv", {"block_r": 512}, backend="cpu")
    assert tuning.resolve_policy("spmv", backend="cpu")["block_r"] == 512
    assert tuning.resolve_policy(
        "spmv", backend="cpu", measured=False
    )["block_r"] == 256
    monkeypatch.setenv("REPRO_TUNE", "0")
    assert not tuning.tuning_enabled()
    assert tuning.resolve_policy("spmv", backend="cpu")["block_r"] == 256


def test_record_rejects_unknown_family_and_knob():
    t = tuning.get_table()
    with pytest.raises(KeyError):
        t.record("nope", {"block_b": 1})
    with pytest.raises(KeyError):
        t.record("spmv", {"block_q": 1})


# ---------------------------------------------------------------------------
# Persistence
# ---------------------------------------------------------------------------
def test_table_round_trips_through_json(tmp_path):
    t = tuning.TuningTable()
    t.record("radix_sort", {"block_b": 8192}, backend="cpu",
             M=1000, N=1000, L=50_000, dtype=np.float32)
    t.record("merge", {"method": "pallas"}, backend="cpu")
    path = t.save(tmp_path / tuning.TABLE_FILENAME)
    t2 = tuning.TuningTable()
    assert t2.load(path) == 2
    assert t2.entries() == t.entries()
    assert t2.fingerprint() == t.fingerprint()
    assert t2.resolve(
        "radix_sort", backend="cpu", M=1000, N=1000, L=50_000,
        dtype=np.float32,
    )["block_b"] == 8192


def test_empty_table_fingerprints_as_prior():
    t = tuning.TuningTable()
    assert t.fingerprint() == "prior"
    t.record("spmv", {"block_r": 128}, backend="cpu")
    assert t.fingerprint() != "prior"


def test_corrupt_table_degrades_to_priors(tmp_path):
    path = tmp_path / tuning.TABLE_FILENAME
    path.write_text("{not json")
    t = tuning.TuningTable()
    with pytest.warns(CacheCorruptionWarning, match="corrupt tuning"):
        assert t.load(path) == 0
    assert t.resolve("spmv", backend="cpu") == tuning.prior_policy(
        "spmv", "cpu"
    )
    # wrong schema version degrades the same way
    path.write_text(json.dumps({"schema": 99, "entries": []}))
    with pytest.warns(CacheCorruptionWarning, match="schema"):
        assert tuning.TuningTable().load(path) == 0


def test_invalid_entries_are_skipped_individually(tmp_path):
    path = tmp_path / tuning.TABLE_FILENAME
    path.write_text(json.dumps({
        "schema": 1,
        "entries": [
            {"family": "spmv", "policy": {"block_r": 512}},
            {"family": "not-a-family", "policy": {"x": 1}},
        ],
    }))
    t = tuning.TuningTable()
    with pytest.warns(CacheCorruptionWarning, match="invalid tuning"):
        assert t.load(path) == 1
    assert t.resolve("spmv", backend="cpu")["block_r"] == 512


def test_env_cache_dir_loads_into_global_table(tmp_path, monkeypatch):
    t = tuning.TuningTable()
    t.record("spmv", {"block_r": 512}, backend="cpu")
    t.save(tmp_path / tuning.TABLE_FILENAME)
    monkeypatch.setenv("REPRO_TUNING_CACHE_DIR", str(tmp_path))
    assert tuning.default_cache_path() == tmp_path / tuning.TABLE_FILENAME
    tuning.reset_table()
    assert tuning.resolve_policy("spmv", backend="cpu")["block_r"] == 512
    assert len(tuning.get_table()) == 1


def test_no_env_means_no_default_cache_path(monkeypatch):
    monkeypatch.delenv("REPRO_TUNING_CACHE_DIR", raising=False)
    assert tuning.default_cache_path() is None


# ---------------------------------------------------------------------------
# Deprecated aliases: single-homed budget
# ---------------------------------------------------------------------------
def test_resident_cap_aliases_pin_to_registry_budget():
    from repro.kernels.merge import ops as merge_ops
    from repro.kernels.segment_sum import ops as ss_ops
    from repro.kernels.spmv_sym import ops as sym_ops

    assert (
        merge_ops.MERGE_RESIDENT_MAX_BYTES
        == ss_ops.FUSED_RESIDENT_MAX_BYTES
        == sym_ops.FUSED_RESIDENT_MAX_BYTES
        == tuning.RESIDENT_BUDGET_BYTES
    )


def test_rebound_alias_still_wins_over_policy(monkeypatch):
    # the historical monkeypatch hook: rebinding the deprecated module
    # constant must still steer the residency guard (tests rely on it)
    from repro.kernels.segment_sum import ops as ss_ops

    monkeypatch.setattr(ss_ops, "FUSED_RESIDENT_MAX_BYTES", 1)
    assert ss_ops._policy(10, np.float32)["resident_max_bytes"] == 1


# ---------------------------------------------------------------------------
# Consumers: dispatch + bit-identical resolution + serving
# ---------------------------------------------------------------------------
def test_dispatch_defaults_resolve_through_table():
    backend = jax.default_backend()
    prior = tuning.prior_value("plan", "method", backend)
    assert dispatch.default_method() == prior
    tuning.get_table().record("plan", {"method": "jnp"}, backend=backend)
    assert dispatch.default_method() == "jnp"
    assert dispatch.resolve_method(None) == "jnp"
    assert dispatch.resolve_method("radix") == "radix"
    tuning.get_table().record("merge", {"method": "pallas"},
                              backend=backend)
    assert dispatch.default_merge_method() == "pallas"
    assert dispatch.resolve_merge_method(None) == "pallas"


def test_resolved_policy_bit_identical_to_explicit_priors():
    rng = np.random.default_rng(0)
    M = N = 50
    L = 400
    rows = np.asarray(rng.integers(0, M, L), np.int32)
    cols = np.asarray(rng.integers(0, N, L), np.int32)
    via_table = dispatch.sorted_permutation(rows, cols, M=M, N=N)
    explicit = dispatch.sorted_permutation(
        rows, cols, M=M, N=N,
        method=tuning.prior_value(
            "plan", "method", jax.default_backend()
        ),
    )
    np.testing.assert_array_equal(
        np.asarray(via_table), np.asarray(explicit)
    )

    from repro.kernels.radix_sort.ops import radix_sort_pair

    pol = tuning.prior_policy("radix_sort")
    np.testing.assert_array_equal(
        np.asarray(radix_sort_pair(rows, cols, M=M, N=N)),
        np.asarray(radix_sort_pair(
            rows, cols, M=M, N=N,
            block_b=int(pol["block_b"]), block_t=int(pol["block_t"]),
            max_bits=int(pol["max_bits"]),
        )),
    )


def test_serving_persists_table_and_reports_fingerprint(
        tmp_path, private_compile_cache):
    svc = serving.PlanService(cache_dir=tmp_path)
    stats = svc.stats()
    assert stats["tuning_fingerprint"] == "prior"
    assert stats["loaded_tuning_entries"] == 0

    tuning.get_table().record("spmv", {"block_r": 512}, backend="cpu")
    svc.save()
    assert (tmp_path / tuning.TABLE_FILENAME).is_file()
    fp = tuning.tuning_fingerprint()
    assert fp != "prior"

    # warm restart: a fresh process-global table + service reload the
    # measured policies (and therefore the same executable-key hash)
    tuning.set_table(tuning.TuningTable())
    svc2 = serving.PlanService(cache_dir=tmp_path)
    assert svc2.loaded_tuning_entries == 1
    assert svc2.stats()["tuning_fingerprint"] == fp


# ---------------------------------------------------------------------------
# Analysis layer: validator + constant lint
# ---------------------------------------------------------------------------
def test_validate_tuning_table_accepts_recorded_entries():
    t = tuning.get_table()
    t.record("radix_sort", {"block_b": 8192, "max_bits": 10},
             backend="cpu", L=1000)
    assert validate_tuning_table(t) == 1


class _StubTable:
    def __init__(self, entries):
        self._entries = entries

    def entries(self):
        return self._entries


@pytest.mark.parametrize("entry,invariant", [
    ({"family": "nope", "policy": {}}, "tuning-unknown-family"),
    ({"family": "spmv", "policy": {"block_q": 1}},
     "tuning-unknown-knob"),
    ({"family": "spmv", "policy": {"block_r": "big"}},
     "tuning-bad-value"),
    ({"family": "spmv", "policy": {"block_r": -4}},
     "tuning-bad-value"),
])
def test_validate_tuning_table_rejects_drifted_entries(entry, invariant):
    with pytest.raises(InvariantViolation) as exc:
        validate_tuning_table(_StubTable([entry]))
    assert invariant in str(exc.value)


def test_tuning_lint_repo_is_clean():
    assert lint_tuning_constants() == []


def test_tuning_lint_flags_rescattered_constants(tmp_path):
    bad = tmp_path / "bad_ops.py"
    bad.write_text(
        "BLOCK_B = 4096\n"
        "MERGE_RESIDENT_MAX_BYTES = 8 << 20\n"
        "CLEAN = tuning.RESIDENT_BUDGET_BYTES\n"
        "def kernel(x, block_b=2048, *, block_t=512, max_bits=None):\n"
        "    return x\n"
    )
    findings = lint_tuning_constants([bad])
    names = sorted(f["name"] for f in findings)
    assert names == ["BLOCK_B", "MERGE_RESIDENT_MAX_BYTES",
                     "block_b", "block_t"]


# ---------------------------------------------------------------------------
# The CLI (prior-only mode — the CI artifact path)
# ---------------------------------------------------------------------------
def test_cli_prior_only_writes_artifact_and_consumes_report(
    tmp_path, capsys
):
    from repro.sparse.analysis.vmem import dump_json, vmem_report
    from repro.sparse.tuning.__main__ import main

    report = tmp_path / "vmem-report.json"
    dump_json(vmem_report(), str(report))
    out = tmp_path / "tuning-table.json"
    rc = main([
        "--prior-only", "--vmem-report", str(report), "--json", str(out),
        "--cache-dir", str(tmp_path / "cache"),
    ])
    assert rc == 0
    captured = capsys.readouterr()
    assert "rows consumed" in captured.out

    artifact = json.loads(out.read_text())
    assert artifact["fingerprint"] == "prior"
    assert artifact["consumed_vmem_rows"] >= 6
    assert set(artifact["priors"]) == set(tuning.registered_families())
    for fam in tuning.registered_families():
        assert artifact["resolved"][fam] == artifact["priors"][fam]
    # the persisted (empty) table loads back cleanly
    t = tuning.TuningTable()
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert t.load(
            tmp_path / "cache" / tuning.TABLE_FILENAME
        ) == 0
    assert t.fingerprint() == "prior"


def test_cli_prior_only_fails_on_diverged_report(tmp_path, capsys):
    from repro.sparse.analysis.vmem import dump_json, vmem_report
    from repro.sparse.tuning.__main__ import main

    report = tmp_path / "vmem-report.json"
    dump_json(vmem_report(), str(report))
    payload = json.loads(report.read_text())
    payload["vmem_report"][0]["budget_bytes"] = 123
    report.write_text(json.dumps(payload))
    assert main(["--prior-only", "--vmem-report", str(report)]) == 1
    assert "FAIL" in capsys.readouterr().err
