"""Unit tests of the ``benchmarks.run --compare`` regression gate.

Pins the ISSUE-5 satellite: a 0.0-us base row (tiny smoke-scale rows
round to the 0.1-us resolution floor on fast CI machines) must be
skipped with a warning, not divide the gate into a spurious failure.
"""
import json

import pytest

from benchmarks.run import COMPARE_EPS_US, compare_rows


def _payload(rows, scale=0.02):
    return {"meta": {"scale": scale},
            "results": {"parts": [dict(r) for r in rows]}}


def test_zero_us_base_row_skipped_with_warning(capsys):
    base = _payload([{"name": "x_method_radix", "us_per_call": 0.0},
                     {"name": "x_fill_fused", "us_per_call": 100.0}])
    results = _payload([{"name": "x_method_radix", "us_per_call": 50.0},
                        {"name": "x_fill_fused", "us_per_call": 101.0}])
    failures = compare_rows(results["results"], base, scale=0.02,
                            tolerance=0.10)
    assert failures == []  # the 0.0-base row must not explode the gate
    err = capsys.readouterr().err
    assert "WARNING" in err and "x_method_radix" in err
    assert "below" in err


def test_real_regression_still_fails():
    base = _payload([{"name": "x_fill_fused", "us_per_call": 100.0}])
    results = _payload([{"name": "x_fill_fused", "us_per_call": 150.0}])
    failures = compare_rows(results["results"], base, scale=0.02,
                            tolerance=0.10)
    assert len(failures) == 1 and "x_fill_fused" in failures[0]


def test_all_rows_below_floor_warns_but_passes(capsys):
    base = _payload([{"name": "x_reuse", "us_per_call": 0.0}])
    results = _payload([{"name": "x_reuse", "us_per_call": 3.0}])
    failures = compare_rows(results["results"], base, scale=0.02,
                            tolerance=0.10)
    assert failures == []
    assert "gate checked nothing" in capsys.readouterr().err


def test_no_matched_rows_is_a_failure():
    base = _payload([{"name": "renamed_row_reuse", "us_per_call": 5.0}])
    results = _payload([{"name": "other_row_reuse", "us_per_call": 5.0}])
    failures = compare_rows(results["results"], base, scale=0.02,
                            tolerance=0.10)
    assert failures and "no gated plan/fill row matched" in failures[0]


def test_scale_mismatch_aborts():
    base = _payload([{"name": "x_reuse", "us_per_call": 5.0}], scale=0.1)
    results = _payload([{"name": "x_reuse", "us_per_call": 5.0}])
    with pytest.raises(SystemExit, match="not comparable"):
        compare_rows(results["results"], base, scale=0.02,
                     tolerance=0.10)


def test_gate_against_synthetic_base_json(tmp_path, capsys):
    """End-to-end through JSON serialization, as CI consumes it."""
    base_file = tmp_path / "base.json"
    base_file.write_text(json.dumps(_payload(
        [{"name": "spgemm_set1_reuse", "us_per_call": 0.0},
         {"name": "spgemm_set1_fill_fused", "us_per_call": 40.0}])))
    base = json.loads(base_file.read_text())
    results = _payload(
        [{"name": "spgemm_set1_reuse", "us_per_call": 12.0},
         {"name": "spgemm_set1_fill_fused", "us_per_call": 44.0}])
    failures = compare_rows(results["results"], base, scale=0.02,
                            tolerance=0.10)
    assert failures == []
    assert COMPARE_EPS_US > 0  # the floor is a real, documented constant


# ---------------------------------------------------------------------------
# Bandwidth roofs of --roofline
# ---------------------------------------------------------------------------
class _Device:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


def test_roofline_peaks_keyed_by_device_kind(monkeypatch):
    from benchmarks import roofline

    rows = [{"name": "k", "bandwidth_gbs": 409.5}, {"name": "no_bw"}]
    v5e = _Device("tpu", "TPU v5 lite")
    assert roofline.annotate_roofline(rows, v5e) == 1
    assert rows[0]["peak_gbs"] == 819.0 and rows[0]["roofline_frac"] == 0.5
    # a TPU the table does not know has no borrowed peak
    with pytest.raises(KeyError, match="TPU v9"):
        roofline.bandwidth_roof(_Device("tpu", "TPU v9"))
    # the CPU's roof is its measured STREAM, never named a device peak
    monkeypatch.setattr(roofline, "_CPU_STREAM_GBS", [10.0])
    rows = [{"name": "k", "bandwidth_gbs": 5.0}]
    roofline.annotate_roofline(rows, _Device("cpu", "cpu"))
    assert rows[0]["cpu_stream_gbs"] == 10.0
    assert rows[0]["cpu_stream_frac"] == 0.5 and "peak_gbs" not in rows[0]
