"""The benchmark's data: generators, computed bytes, the reference and
the shape of ``BENCHMARK.json``."""
import json
import re
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import check, harness, kernel_bytes, oracle, peaks  # noqa: E402
from bench.generators import fem_p1, table41  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("n", [1, 2, 3, 5])
def test_fem_generator_sizes_and_symmetry(n):
    ii, jj, (M, N), state = fem_p1.generate({"n": n}, 2**31 + 3)
    assert ii.size == jj.size == 96 * n ** 3
    assert M == N == (n + 1) ** 3
    assert ii.min() == 1 and ii.max() == M and jj.max() == N
    pairs = set(zip(ii.tolist(), jj.tolist()))
    assert all((b, a) in pairs for a, b in pairs)
    # the assembled stiffness matrix: symmetric, rows summing to zero,
    # a positive diagonal (Neumann Laplacian of a P1 mesh)
    A = np.zeros((M, N))
    np.add.at(A, (ii - 1, jj - 1), fem_p1.value_set(state, 3, 0))
    np.testing.assert_allclose(A, A.T, atol=1e-12)
    np.testing.assert_allclose(A.sum(axis=1), 0, atol=1e-12)
    assert (np.diag(A) > 0).all()


def test_fem_generator_follows_the_seed():
    a = fem_p1.generate({"n": 2}, 7)
    b = fem_p1.generate({"n": 2}, 7)
    c = fem_p1.generate({"n": 2}, 8)
    # the mesh is the same for every seed, in natural vertex order
    assert np.array_equal(a[0], c[0]) and np.array_equal(a[1], c[1])
    assert a[0][:4].tolist() == [1, 1, 1, 1] and a[1][:4].tolist() == [1, 2, 5, 14]
    v0 = fem_p1.value_set(a[3], 7, 0)
    assert np.array_equal(v0, fem_p1.value_set(b[3], 7, 0))
    assert not np.array_equal(v0, fem_p1.value_set(a[3], 7, 1))
    assert not np.array_equal(v0, fem_p1.value_set(c[3], 8, 0))


def test_kuhn_tetrahedra_fill_the_cube():
    vols = [abs(np.linalg.det(np.hstack([np.ones((4, 1)), c]))) / 6
            for c in fem_p1._kuhn_offsets().astype(float)]
    assert sum(vols) == pytest.approx(1.0)


def test_table41_generator():
    cfg = {"siz": 100, "nnz_row": 5, "nrep": 2}
    ii, jj, shape, state = table41.generate(cfg, 2**40 + 1, 3)
    assert shape == (100, 100) and ii.size == jj.size == 1000
    assert np.array_equal(np.bincount(ii, minlength=101)[1:],
                          np.full(100, 10))
    again = table41.generate(cfg, 2**40 + 1, 3)
    other = table41.generate(cfg, 2**40 + 1, 4)
    assert np.array_equal(ii, again[0]) and np.array_equal(jj, again[1])
    assert not np.array_equal(jj, other[1])
    v = table41.value_set(state, 1, 0)
    assert v.shape == (1000,) and 0.5 <= v.min() and v.max() < 1.5


def test_computed_bytes():
    assert kernel_bytes.fill_bytes(10, 10) == 160
    assert kernel_bytes.fill_bytes(2_500_000, 2_500_000) == 40_000_000
    assert kernel_bytes.radix_histogram_bytes(1000) == 4_000
    assert kernel_bytes.radix_placement_bytes(1000) == 8_000


def test_peaks_refuse_an_unknown_device():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v99")


def test_reference_matches_a_dense_sum():
    rng = np.random.default_rng(0)
    ii = rng.integers(0, 7, 200)
    jj = rng.integers(0, 5, 200)
    ss = rng.standard_normal(200)
    ref = oracle.StructureReference(ii, jj, 7, 5)
    pr, ir, jc = ref.values(ss), ref.indices, ref.indptr
    dense = np.zeros((7, 5))
    np.add.at(dense, (ii, jj), ss)
    got = np.zeros((7, 5))
    for c in range(5):
        for k in range(jc[c], jc[c + 1]):
            got[ir[k], c] = pr[k]
    np.testing.assert_allclose(got, dense, atol=1e-12)
    assert all(np.all(np.diff(ir[jc[c]:jc[c + 1]]) > 0) for c in range(5))


def test_compare_and_bf16_control():
    rng = np.random.default_rng(1)
    ii, jj = rng.integers(0, 30, 2000), rng.integers(0, 30, 2000)
    ss = rng.random(2000) + 0.5
    ref = oracle.StructureReference(ii, jj, 30, 30)
    exact = {"nnz": ref.nnz, "indptr": ref.indptr, "indices": ref.indices,
             "data": ref.values(ss).astype(np.float32)}
    good = check.compare(exact, ref, ss)
    assert good["structure_mismatches"] == 0
    assert good["data_rel_err"] < 1e-6
    low = check.compare(dict(exact, data=check.bf16_data(ref, ss)), ref, ss)
    assert low["data_rel_err"] > 1e-3
    bad = dict(exact, indices=exact["indices"].copy())
    bad["indices"][3] += 1
    assert check.compare(bad, ref, ss)["structure_mismatches"] == 1


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_is_whole():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    bench = ROOT / "bench"
    for c in SPEC["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and NAME.match(c["name"])
        assert (bench / "generators" / f"{cfg['generator']}.py").is_file()
        assert set(cfg["limits"]) == {"structure_mismatches",
                                      "data_rel_err"}
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"])
        assert harness.load_reader(bench, m["name"]).read
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", [w])
    for cell in SPEC["workloads"]:
        assert NAME.match(cell["name"]) and cell["chips"] == 1
        assert (bench / "traffic" / f"{cell['traffic']}.json").is_file()
        names = {m["name"] for m in harness.cell_metrics(
            SPEC, cell["name"], False)}
        assert "setup_s" in names and len(names) >= 2
        assert harness.cell_metrics(SPEC, cell["name"], True)
