"""The host spans of the served request path, read back from a profiler
trace (a cold and a warm ``PlanService.assemble`` and one ``fsparse``),
and the device scopes, read from the compiled programs' metadata."""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import spans
from repro.sparse import (PlanService, cached_product_plan, fsparse, ops,
                          plan, plan_cache_clear)
from repro.sparse.pattern import _merge_sorted_streams
from repro.sparse.serving import _served_fill

L, M, N = 600, 40, 30
#: the stage spans of a cold request, in the order it crosses them
STAGES = [spans.EXPAND, spans.VALIDATE, spans.UPLOAD, spans.PLAN_KEY,
          spans.PLAN_CACHE, spans.PLAN, spans.EXEC_CACHE, spans.COMPILE,
          spans.FILL]
#: those of a warm one: its raw indices are compared, only values go up
WARM_STAGES = [spans.PLAN_KEY, spans.UPLOAD, spans.EXEC_CACHE, spans.FILL]


def _host_spans(logdir) -> list:
    """``(name, start, end, stats)`` of every ``sparse.*`` host event
    of the trace under ``logdir``, by start."""
    (path,) = glob.glob(str(logdir / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    out = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
           for plane in data.planes if plane.name.startswith("/host:")
           for line in plane.lines for e in line.events
           if e.name.startswith("sparse.")]
    return sorted(out, key=lambda s: s[1])


def _inside(outer, events) -> list:
    return [e for e in events
            if outer[1] <= e[1] and e[2] <= outer[2] and e is not outer]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    rng = np.random.default_rng(13)
    ii = rng.integers(1, M + 1, L)
    jj = rng.integers(1, N + 1, L)
    plan_cache_clear()
    svc = PlanService()
    logdir = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(logdir), profiler_options=opts)
    try:
        for _ in range(3):   # cold, then warm twice
            S = svc.assemble(ii, jj, rng.random(L), (M, N))
            S.data.block_until_ready()
        fsparse(ii, jj, rng.random(L), (M, N)).data.block_until_ready()
    finally:
        jax.profiler.stop_trace()
        plan_cache_clear()
    events = _host_spans(logdir)
    outer = {name: [e for e in events if e[0] == name]
             for name in (spans.ASSEMBLE, spans.FSPARSE)}
    return events, outer


def test_every_stage_in_order_inside_its_request(traced):
    events, outer = traced
    cold, *warm = outer[spans.ASSEMBLE]
    (one_shot,) = outer[spans.FSPARSE]
    assert [e[0] for e in _inside(cold, events)] == STAGES
    for request in warm:
        assert [e[0] for e in _inside(request, events)] == WARM_STAGES
    assert [e[0] for e in _inside(one_shot, events)] == [
        spans.EXPAND, spans.VALIDATE, spans.UPLOAD, spans.PLAN, spans.FILL]


def test_plan_and_compile_nest_in_their_cache_lookups(traced):
    events, outer = traced
    cold = {e[0]: e for e in _inside(outer[spans.ASSEMBLE][0], events)}
    assert _inside(cold[spans.PLAN_CACHE], events) == [cold[spans.PLAN]]
    assert _inside(cold[spans.EXEC_CACHE], events) == [cold[spans.COMPILE]]


def test_request_stats(traced):
    events, outer = traced
    cold, warm, _ = outer[spans.ASSEMBLE]
    assert warm[3]["request"] == cold[3]["request"] + 1
    assert cold[3]["L"] == warm[3]["L"] == outer[spans.FSPARSE][0][3]["L"] == L


@pytest.mark.parametrize("request_no,upload,plan_key,hit", [
    # cold: int32 rows and cols, float32 values up; int32 rows and cols
    # keyed
    (0, 12 * L, 8 * L, 0),
    # warm: float32 values up only; the caller's int64 indices compared
    (1, 4 * L, 16 * L, 1),
    (2, 4 * L, 16 * L, 1),
])
def test_stage_stats(traced, request_no, upload, plan_key, hit):
    events, outer = traced
    inner = {e[0]: e for e in _inside(outer[spans.ASSEMBLE][request_no],
                                      events)}
    assert inner[spans.UPLOAD][3]["bytes"] == upload
    assert inner[spans.PLAN_KEY][3]["bytes"] == plan_key
    assert inner[spans.PLAN_KEY][3]["hit"] == hit


def _programs():
    """One small lowered program per device scope, by name."""
    rng = np.random.default_rng(5)
    rows = jnp.asarray(rng.integers(0, N, L), jnp.int32)
    cols = jnp.asarray(rng.integers(0, N, L), jnp.int32)
    pat = plan(rows, cols, (N, N))
    S = pat.assemble(jnp.ones(L))
    return {
        "plan": plan.lower(rows, cols, shape=(N, N)),
        "fill": _served_fill(pat).lower(pat.perm, pat.slot, jnp.ones(L)),
        "merge": _merge_sorted_streams.lower(
            pat.srows, pat.scols, pat.perm, rows[:7], cols[:7],
            jnp.int32(L), M=N, N=N, nzmax=L + 7, method=None,
            merge_method=None),
        "spmv": jax.jit(lambda x: ops.matmul(S, x)).lower(jnp.ones(N)),
        "multiply": jax.jit(cached_product_plan(S, S).multiply).lower(
            S.data, S.data),
    }


@pytest.fixture(scope="module")
def compiled():
    return {k: v.compile().as_text() for k, v in _programs().items()}


@pytest.mark.parametrize("program,scope", [
    ("plan", "plan.sort"), ("plan", "plan.compress"), ("fill", "fill"),
    ("merge", "merge"), ("spmv", "spmv"), ("multiply", "multiply"),
])
def test_device_scope_reaches_op_metadata(compiled, program, scope):
    path = f'op_name="[^"]*/{re.escape(scope)}/'
    assert re.search(path, compiled[program])


@pytest.mark.parametrize("program,module", [("plan", "jit_plan"),
                                            ("fill", "jit_scatter")])
def test_benchmark_module_names_stay(compiled, program, module):
    assert compiled[program].startswith(f"HloModule {module},")
