"""Trace reduction on a small recorded trace: busy union, idle share,
attribution of operations to modules and of idle gaps to host spans,
and the planes of several chips read apart."""
import json
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness, tracereduce  # noqa: E402

DATA = Path(__file__).resolve().parent / "data" / "small_trace.json"
BENCH = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def trace():
    raw = json.loads(DATA.read_text())
    ops = [tracereduce.Event(*op) for op in raw["ops"]]
    mods = [tracereduce.Event(n, s, d) for n, s, d in raw["modules"]]
    host = [tracereduce.Event(n, s, d) for n, s, d in raw["host"]]
    return tracereduce.build(ops, mods, host)


@pytest.mark.parametrize("label,name", [
    ("%digit_placement.11 = s32[19552,128]{1,0:T(8,128)S(1)} custom-call("
     "s32[19552,128]{1,0:T(8,128)S(1)} %fusion.4)", "digit_placement.11"),
    ("%fusion = f32[2500000]{0:T(1024)} fusion(f32[2500000]{0:T(1024)} "
     "%vals.1), kind=kCustom", "fusion"),
    ("copy.4", "copy.4"),
])
def test_op_named_by_its_hlo_instruction(label, name):
    assert tracereduce.hlo_name(label) == name


def test_union_merges_overlaps():
    assert tracereduce.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [
        (0, 4), (5, 7)]


def test_busy_and_idle_share(trace):
    lo, hi = trace.window()
    assert (lo, hi) == (0, 10000)
    assert tracereduce.busy(trace.ops, lo, hi) == 4500
    assert tracereduce.busy(trace.ops, 2000, 6500) == 2000
    assert tracereduce.gaps(trace.ops, lo, hi) == [
        (0, 1000), (3500, 6000), (7500, 9000), (9500, 10000)]


def test_ops_attributed_to_enclosing_module(trace):
    by_name = {o.name: o.module for o in trace.ops}
    assert by_name["digit_placement.5"] == "jit_plan"
    assert by_name["copy.4"] == "jit_scatter"
    assert by_name["copy.9"] == "jit_other"   # a module stat is kept
    assert tracereduce.op_time(trace.ops, 0, 10000,
                               modules=("jit_plan",)) == 2500
    assert tracereduce.op_time(trace.ops, 0, 10000,
                               modules=("jit_scatter",)) == 1500
    # a launch is counted by its HLO name alone (not copy.6 beside it)
    hist = tracereduce.kernel_ops(trace.ops, "digit_block_histogram",
                                  0, 10000)
    assert [o.name for o in hist] == ["digit_block_histogram.2"]
    assert len(tracereduce.kernel_ops(trace.ops, "digit_placement",
                                      0, 10000)) == 1


def test_idle_gaps_labelled_by_host_span(trace):
    lo, hi = trace.window()
    got = dict(tracereduce.idle_by_host(trace.ops, trace.host, lo, hi))
    assert got == pytest.approx({"bench.request": 1e-6,
                                 "outside any span": 4.5e-6})
    top = dict(tracereduce.top_ops(trace.ops, lo, hi))
    assert len(top) == 7
    assert top["jit_plan/digit_block_histogram.2"] == pytest.approx(5e-7)
    assert top["jit_other/copy.9"] == pytest.approx(5e-7)
    assert sum(top.values()) == pytest.approx(5.2e-6)
    assert len(tracereduce.top_ops(trace.ops, lo, hi, n=2)) == 2


def _ctx(trace):
    cfg = {"L": 1000, "M": 10, "N": 10}
    peak = {"hbm_bytes_s": 1e12}
    return harness.Context(cfg=cfg, setup_s=1.0,
                           latencies=[0.004, 0.003], triplets=1000,
                           window_s=0.01, trace=trace, peak=peak)


def test_metric_readers_on_the_small_trace(trace):
    ctx = _ctx(trace)
    read = lambda name: harness.load_reader(BENCH, name).read(ctx)  # noqa: E731
    assert read("idle_frac.refill") == pytest.approx(0.55)
    assert read("idle_frac.new") == pytest.approx(0.55)
    # request spans [500, 4500) and [5000, 8000) hold 2500 and 1500 ns
    # of device time: 1500 ns of host time each
    assert read("frontend_host_ms.refill") == pytest.approx(1500 / 1e6)
    assert read("fill_device_ms.refill") == pytest.approx(1500 / 2 / 1e6)
    assert read("plan_device_ms.new") == pytest.approx(2500 / 2 / 1e6)
    # 1000 triplets: 4000 bytes read by the histogram kernel in 500 ns
    # and 8000 by the placement kernel in 1000 ns, against 1e12 B/s
    assert read("radix_roofline.new") == pytest.approx(
        100 * 12000 / 1500e-9 / 1e12)
    # two requests of 16000 computed fill bytes in 1500 ns of fill
    assert read("fill_roofline.refill") == pytest.approx(
        100 * 32000 / 1500e-9 / 1e12)


def test_readers_return_nothing_where_nothing_ran(trace):
    empty = tracereduce.Trace(
        [tracereduce.Event("fusion.1", 100, 10, "jit_other")], trace.host)
    ctx = _ctx(empty)
    for name in ("fill_device_ms.refill", "fill_roofline.refill",
                 "plan_device_ms.new", "radix_roofline.new"):
        assert harness.load_reader(BENCH, name).read(ctx) is None


def _planes(raw, chips):
    """The small trace as ``ProfileData`` planes: each of ``chips``
    device planes holds its ops and modules, 100 ns later per chip; the
    host plane holds the spans with stats on the first request."""
    def ev(name, start, dur, stats=()):
        return NS(name=name, start_ns=start, duration_ns=dur,
                  stats=list(stats))

    planes = [NS(name="/host:metadata", lines=[])]
    for d in chips:
        ops = [ev(n, s + 100 * d, t, [("hlo_module", m)] if m else [])
               for n, s, t, m in raw["ops"]]
        mods = [ev(n, s + 100 * d, t) for n, s, t in raw["modules"]]
        planes.append(NS(name=f"/device:TPU:{d}", lines=[
            NS(name="XLA Modules", events=mods),
            NS(name="XLA Ops", events=ops)]))
    host = [ev(n, s, t) for n, s, t in raw["host"]]
    host.append(ev("sparse.upload", 600, 100, [("bytes", 4000)]))
    host.append(ev("sparse.plan_key", 700, 100,
                   [("bytes", 8000), ("hit", 0), ("hit", 1)]))
    planes.append(NS(name="/host:CPU", lines=[NS(name="python",
                                                 events=host)]))
    return planes


def test_planes_of_several_chips_read_apart(trace):
    raw = json.loads(DATA.read_text())
    one = tracereduce.from_planes(_planes(raw, [0]), [0])
    assert one.ops == trace.ops
    assert list(one.ops_by_device) == [0]
    four = tracereduce.from_planes(_planes(raw, [0, 1, 2, 3]), [2, 0, 1, 3])
    # ``ops`` is the first chip of the cell, as on one chip
    assert list(four.ops_by_device) == [2, 0, 1, 3]
    assert four.ops is four.ops_by_device[2]
    assert four.ops_by_device[0] == one.ops
    for d, ops in four.ops_by_device.items():
        assert [o.start for o in ops] == [o.start + 100 * d for o in one.ops]
        assert [o.module for o in ops] == [o.module for o in one.ops]
    lo, hi = one.window()
    assert tracereduce.busy(four.ops_by_device[3], lo, hi) == 4500


def test_span_stats_kept_as_the_trace_holds_them():
    raw = json.loads(DATA.read_text())
    tr = tracereduce.from_planes(_planes(raw, [0]), [0])
    (upload,) = tr.spans("sparse.upload")
    (key,) = tr.spans("sparse.plan_key")
    assert upload.stats == {"bytes": 4000}
    # a stat set again (``set_metadata``) keeps its last value
    assert key.stats == {"bytes": 8000, "hit": 1}
    assert all(o.stats == {} for o in tr.ops)


def test_a_chip_with_no_operation_is_an_error():
    raw = json.loads(DATA.read_text())
    with pytest.raises(ValueError, match="TPU:1"):
        tracereduce.from_planes(_planes(raw, [0]), [0, 1])
