"""From a profiler trace to busy time, idle gaps and per-module time.

The benchmark wraps its measured window in a host span ``bench.window``
and every request in ``bench.request`` (``jax.profiler.TraceAnnotation``),
so the device's operations and the harness's spans share the profiler's
clock.  :func:`load` reads the ``.xplane.pb`` that
``jax.profiler.stop_trace`` wrote: the operations of every chip of the
cell, and the host's spans with the stats they carry (``bytes`` and
``hit`` of ``sparse.plan_key``, ...).  Everything else works on plain
lists of :class:`Event` so that it can be checked on a small recorded
trace.

Times are in nanoseconds.
"""
from __future__ import annotations

import glob
from pathlib import Path
from types import MappingProxyType
from typing import NamedTuple

#: no stats, or no chips: the default of an event or a trace built by hand
_EMPTY = MappingProxyType({})


class Event(NamedTuple):
    name: str
    start: float   # ns on the profiler's clock
    dur: float     # ns
    module: str = ""  # the XLA module (jitted program) a device op ran in
    #: a host span's stats as the trace holds them (ints, floats,
    #: strings; a stat set twice keeps its last value); empty for ops
    stats: dict = _EMPTY

    @property
    def end(self) -> float:
        return self.start + self.dur


class Trace(NamedTuple):
    ops: list          # device operations of the cell's first chip, [Event]
    host: list         # host spans and annotations, [Event]
    #: device id -> its operations, for every chip of the cell that was
    #: read, in the cell's order (the first chip's list is ``ops``)
    ops_by_device: dict = _EMPTY

    def spans(self, name: str) -> list:
        return [e for e in self.host if e.name == name]

    def window(self) -> tuple:
        """``(start, end)`` of the ``bench.window`` span."""
        (w,) = self.spans("bench.window")
        return w.start, w.end


#: the device plane's line that holds one event per XLA operation, and
#: the one that holds one event per executed XLA module
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def _stat(event, key):
    for k, v in event.stats:
        if k == key:
            return v
    return None


def load(logdir, device_ids=(0,)) -> Trace:
    """The operations of ``/device:TPU:<id>`` for each of
    ``device_ids`` (the cell's chips, first one first) and the host's
    spans, from the newest trace under ``logdir``."""
    import jax

    paths = sorted(glob.glob(str(Path(logdir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    return from_planes(data.planes, device_ids)


def from_planes(planes, device_ids=(0,)) -> Trace:
    """:func:`load` on planes as ``jax.profiler.ProfileData`` gives
    them (each with a ``name`` and ``lines`` of events)."""
    planes = list(planes)
    chips = {}
    for d in device_ids:
        name = f"/device:TPU:{d}"
        ops, modules = [], []
        for plane in planes:
            if plane.name != name:
                continue
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend(Event(e.name, e.start_ns, e.duration_ns,
                                     str(_stat(e, "hlo_module") or ""))
                               for e in line.events)
                elif line.name == MODULES_LINE:
                    modules.extend(Event(e.name, e.start_ns, e.duration_ns)
                                   for e in line.events)
        if not ops:
            seen = {p.name: [ln.name for ln in p.lines] for p in planes}
            raise ValueError(f"the trace holds no operation on {name}; "
                             f"planes and lines: {seen}")
        chips[d] = (ops, modules)
    host = [Event(e.name, e.start_ns, e.duration_ns, "", dict(e.stats))
            for plane in planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.duration_ns > 0]
    return build_chips(chips, host)


def build(ops, modules, host) -> Trace:
    """A :class:`Trace` of one chip (device 0) from events as the trace
    file holds them: each op named by its HLO instruction, and given its
    module."""
    return build_chips({0: (ops, modules)}, host)


def build_chips(chips: dict, host) -> Trace:
    """:func:`build` for several chips: ``chips`` maps each device id,
    in the cell's order, to its ``(ops, modules)``."""
    by_device = {
        d: attach_modules([Event(hlo_name(o.name), o.start, o.dur, o.module)
                           for o in ops], modules)
        for d, (ops, modules) in chips.items()}
    return Trace(next(iter(by_device.values())), host, by_device)


def hlo_name(label: str) -> str:
    """``%fusion.51 = s32[2500000]{0} fusion(...)`` -> ``fusion.51``: a
    TPU trace names each op event by its whole HLO instruction; the
    reduction keys ops by the instruction's name alone."""
    if label.startswith("%") and " = " in label:
        return label[1:label.index(" = ")]
    return label


def module_name(label: str) -> str:
    """``jit_scatter(123)`` -> ``jit_scatter``: an XLA module event's
    name without the program id."""
    return label.split("(", 1)[0]


def attach_modules(ops: list, modules: list) -> list:
    """Give every op that has no ``module`` the module event that
    encloses its start."""
    mods = sorted(modules, key=lambda m: m.start)
    out, j = [], 0
    for op in sorted(ops, key=lambda o: o.start):
        if op.module:
            out.append(op)
            continue
        while j < len(mods) and mods[j].end <= op.start:
            j += 1
        name = ""
        if j < len(mods) and mods[j].start <= op.start:
            name = module_name(mods[j].name)
        out.append(op._replace(module=name))
    return out


def union(intervals) -> list:
    """Merged, sorted ``[(start, end)]`` of possibly overlapping
    intervals."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def covered(merged, lo: float, hi: float) -> float:
    """Length of ``[lo, hi)`` that the merged intervals cover."""
    return sum(e - s for s, e in clip(merged, lo, hi))


def busy(ops, lo: float, hi: float) -> float:
    """ns of ``[lo, hi)`` in which some operation ran on the device."""
    return covered(union((o.start, o.end) for o in ops), lo, hi)


def gaps(ops, lo: float, hi: float) -> list:
    """``[(start, end)]`` of ``[lo, hi)`` in which the device ran
    nothing."""
    out, t = [], lo
    for s, e in clip(union((o.start, o.end) for o in ops), lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def idle_by_host(ops, host, lo: float, hi: float, n: int = 10,
                 labelled: int = 5000) -> list:
    """``[[host span, seconds]]``: the device's idle time in the window,
    summed by what the host was doing (the innermost host span at the
    middle of each gap), largest first.  Only the ``labelled`` longest
    gaps are looked up; the rest are summed as ``shorter gaps``."""
    import numpy as np

    spans = [h for h in host if h.name != "bench.window"]
    starts = np.array([h.start for h in spans], dtype=np.float64)
    ends = np.array([h.end for h in spans], dtype=np.float64)
    durs = ends - starts
    agg: dict = {}
    found = sorted(gaps(ops, lo, hi), key=lambda g: g[0] - g[1])
    for i, (s, e) in enumerate(found):
        name = "shorter gaps"
        if i < labelled:
            t = (s + e) / 2
            cover = np.flatnonzero((starts <= t) & (ends > t))
            name = (spans[cover[np.argmin(durs[cover])]].name if cover.size
                    else "outside any span")
        agg[name] = agg.get(name, 0.0) + (e - s) / 1e9
    return [[k, v] for k, v in sorted(agg.items(), key=lambda kv: -kv[1])][:n]


def top_ops(ops, lo: float, hi: float, n: int = 10) -> list:
    """``[[module/op name, seconds]]``: device time by operation inside
    the window, largest first."""
    agg: dict = {}
    for o in ops:
        d = covered([(o.start, o.end)], lo, hi)
        if d > 0:
            key = f"{o.module}/{o.name}" if o.module else o.name
            agg[key] = agg.get(key, 0.0) + d / 1e9
    return [[k, v] for k, v in sorted(agg.items(), key=lambda kv: -kv[1])][:n]


def op_time(ops, lo: float, hi: float, *, modules=()) -> float:
    """ns of device time inside ``[lo, hi)`` of the operations whose
    module contains one of ``modules`` (their union, so nested events
    count once)."""
    sel = [(o.start, o.end) for o in ops
           if any(m in o.module for m in modules)]
    return covered(union(sel), lo, hi)


def kernel_ops(ops, kernel: str, lo: float, hi: float) -> list:
    """The launches of ``kernel`` starting inside ``[lo, hi)``: the ops
    whose HLO name is ``kernel`` or ``kernel.<n>``, so that an event
    that only mentions the kernel in its metadata is not counted."""
    return [o for o in ops if lo <= o.start < hi
            and (o.name == kernel or o.name.startswith(kernel + "."))]
