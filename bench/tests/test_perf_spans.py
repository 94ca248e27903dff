"""The readers of the program's stage spans on a synthetic trace: self
time per request of the window, nested stages left out, span stats per
request, nothing read where no span is, and every name matched being
one the program opens."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import harness, spantime, tracereduce  # noqa: E402
from repro.core import spans  # noqa: E402

BENCH = ROOT / "bench"
READERS = ("expand_host_ms.refill", "validate_host_ms.refill",
           "upload_host_ms.refill", "plan_key_host_ms.refill",
           "dispatch_host_ms.refill")

E = tracereduce.Event
# (span, start, duration) in ns; a cold request, a warm one, and a
# request that the window's end cuts
COLD = [("bench.request", 1000, 9500), ("sparse.assemble", 1000, 9000),
        ("sparse.expand", 1100, 500), ("sparse.validate", 1600, 400),
        ("sparse.upload", 2000, 300), ("TransferToDevice", 2050, 200),
        ("sparse.plan_key", 2300, 200), ("sparse.plan_cache", 2500, 2000),
        ("sparse.plan", 2600, 1800), ("sparse.exec_cache", 4500, 3500),
        ("sparse.compile", 4600, 3300), ("sparse.fill", 8000, 100)]
WARM = [("bench.request", 20000, 2500), ("sparse.assemble", 20000, 2000),
        ("sparse.expand", 20100, 500), ("sparse.validate", 20600, 400),
        ("sparse.upload", 21000, 300), ("sparse.plan_key", 21300, 200),
        ("sparse.plan_cache", 21500, 100), ("sparse.exec_cache", 21600, 50),
        ("sparse.fill", 21650, 100)]
CUT = [("bench.request", 95000, 10000), ("sparse.expand", 95100, 7000),
       ("sparse.fill", 103000, 900)]
# set-up before the window, and host work between requests
OUTSIDE = [("sparse.expand", 50000, 4000), ("sparse.fill", 60000, 900)]


def _ctx(spans_):
    host = [E("bench.window", 0, 100000)] + [
        E(*s) if len(s) == 3 else E(*s[:3], "", s[3]) for s in spans_]
    ops = [E("fusion", 8200, 1000, "jit_scatter"),
           E("fusion", 21800, 150, "jit_scatter")]
    trace = tracereduce.build(ops, [], host)
    return harness.Context(cfg={"L": 10}, setup_s=1.0, latencies=[],
                           triplets=10, window_s=1e-4, trace=trace,
                           peak={})


def _read(name, ctx):
    return harness.load_reader(BENCH, name).read(ctx)


@pytest.mark.parametrize("name,ns", [
    ("expand_host_ms.refill", 500),
    ("validate_host_ms.refill", 400),
    # a runtime span inside the copy is the copy's own time
    ("upload_host_ms.refill", 300),
    # the key, then the lookup less the planning nested in it
    ("plan_key_host_ms.refill", (200 + 200 + 200 + 100) / 2),
    # the lookup less the compile nested in it, then the dispatch
    ("dispatch_host_ms.refill", (200 + 100 + 50 + 100) / 2),
])
def test_self_time_per_request_of_the_window(name, ns):
    ctx = _ctx(COLD + WARM + CUT + OUTSIDE)
    assert len(ctx.requests()) == 2
    assert _read(name, ctx) == pytest.approx(ns / 1e6)


def test_nested_stage_left_out_of_the_lookup_around_it():
    ctx = _ctx(COLD)
    assert spantime.self_ms(ctx, (spans.PLAN_CACHE,)) == pytest.approx(2e-4)
    assert spantime.self_ms(ctx, (spans.PLAN,)) == pytest.approx(1.8e-3)
    assert spantime.self_ms(ctx, (spans.EXEC_CACHE,)) == pytest.approx(2e-4)
    assert spantime.self_ms(ctx, (spans.COMPILE,)) == pytest.approx(3.3e-3)


# the same requests with the stats the program puts on its spans: a
# cold request's key misses and uploads rows, cols and values, a warm
# one hits and uploads the values alone
STATS = {("sparse.upload", 2000): {"bytes": 120},
         ("sparse.plan_key", 2300): {"bytes": 80, "hit": 0},
         ("sparse.upload", 21000): {"bytes": 40},
         ("sparse.plan_key", 21300): {"bytes": 80, "hit": 1},
         ("sparse.assemble", 20000): {"request": 7, "L": 10}}
WITH_STATS = [s + (STATS[s[:2]],) if s[:2] in STATS else s
              for s in COLD + WARM + CUT + OUTSIDE]


def test_span_stats_kept_through_build():
    ctx = _ctx(WITH_STATS)
    hits = [(s.start, s.stats) for s in ctx.trace.spans(spans.PLAN_KEY)]
    assert hits == [(2300, {"bytes": 80, "hit": 0}),
                    (21300, {"bytes": 80, "hit": 1})]
    assert ctx.trace.spans(spans.PLAN)[0].stats == {}


@pytest.mark.parametrize("names,key,per_request", [
    ((spans.UPLOAD,), "bytes", (120 + 40) / 2),
    ((spans.PLAN_KEY,), "hit", 1 / 2),
    ((spans.PLAN_KEY,), "bytes", 80),
    ((spans.UPLOAD, spans.PLAN_KEY), "bytes", (120 + 40 + 80 + 80) / 2),
    ((spans.ASSEMBLE,), "L", 10 / 2),
])
def test_span_stat_per_request_of_the_window(names, key, per_request):
    ctx = _ctx(WITH_STATS)
    assert spantime.stat_per_request(ctx, names, key) == pytest.approx(
        per_request)


def test_no_stat_read_where_no_span_carries_it():
    assert spantime.stat_per_request(_ctx(WITH_STATS), (spans.FILL,),
                                     "bytes") is None
    assert spantime.stat_per_request(_ctx(COLD + WARM), (spans.UPLOAD,),
                                     "bytes") is None
    # stats outside the window's requests are not read
    outside = [("sparse.upload", 50000, 10, {"bytes": 4})]
    assert spantime.stat_per_request(_ctx(COLD[:1] + outside),
                                     (spans.UPLOAD,), "bytes") is None


@pytest.mark.parametrize("name", READERS)
def test_nothing_read_where_no_span_is(name):
    # a program without stage spans, and stage spans only outside the
    # window's requests
    bare = [s for s in COLD + WARM if not s[0].startswith("sparse.")]
    assert _read(name, _ctx(bare)) is None
    assert _read(name, _ctx(bare + CUT + OUTSIDE)) is None


def test_every_matched_name_is_a_program_span():
    program = {v for k, v in vars(spans).items()
               if k.isupper() and isinstance(v, str)}
    assert all(n.startswith(spantime.PREFIX) for n in program)
    for name in READERS:
        matched = harness._patterns(harness.load_reader(BENCH, name))
        assert matched, name
        for names in matched.values():
            assert set(names) <= program, (name, names)
