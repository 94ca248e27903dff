"""The traffic loops: closed loops over the program.

A traffic mix (``bench/traffic/<name>.json``) names a ``loop`` and its
parameters; a configuration (``bench/configs/<name>.json``) names the
``generator`` module (``bench/generators/<name>.py``) that makes its
triplets from the seed.  Every loop is a closed loop with one caller:
the next request is sent when the last one has returned and its result
is ready on the device.  Two loops are built in:

* ``refill``: one hot structure through ``PlanService.assemble`` with
  a new value vector every request, cycled from ``value_sets`` vectors
  made in set-up.  The planner is bypassed; the front end and the
  served fill do the work.
* ``new``: ``fsparse`` on a structure never seen before, every request.
  A producer thread makes structure ``k`` from ``(seed, k)`` ahead of
  the caller, ``queue_depth`` deep, so making it is not timed; the
  caller's waits on it are reported as ``generator_wait_s``.

Any other loop name ``<loop>`` is the class ``LOOP`` of the file
``bench/traffic/<loop>.py``, a subclass of :class:`Loop`
(``bench.harness.load_loop``), so a cell brings a loop of its own as a
new file.  The protocol, in the order the harness calls it:

* ``Loop(cfg, traffic, seed, generator)``: the configuration and the
  traffic mix as parsed JSON, the run's seed, the generator module;
* ``setup()``: make the inputs from the seed, plan, compile, and warm
  every program the window will run (timed as ``setup_s``);
* ``request(r)``: request ``r`` of the window (0, 1, ...), returning
  when its result is ready on the device; it offers results to
  ``self.sample``;
* ``finish()``: after the window and the memory reading, copy the
  sample to the host and drop the program's device state;
* ``close()``: stop whatever the loop started; called on every exit,
  also after a failure, and safe to call twice;
* ``check(control) -> list``: one dict of numbers per sampled result
  (:func:`bench.check.compare`), with the reference in bfloat16 put in
  the program's place where ``control`` is true;
* ``triplets()``: triplets per request, for the rates;
* ``wait_s``: seconds the caller waited on the loop's own generator
  (``generator_wait_s``).

Two rules.  A loop whose result is not one CSC (a ``ShardedCSC``, a
batch) converts it to the CSC the check compares after the window
(in ``finish`` or ``check``), never inside it.  A loop keeps its set-up
(planning, compiling, warming) out of the window, as
:class:`RefillLoop` does: nothing compiles inside the window.

Each loop keeps a seeded reservoir sample of ``check_sample`` results
and rebuilds their references once the window has closed.
"""
from __future__ import annotations

import queue
import threading

import numpy as np

from . import check, oracle


def _host(S) -> dict:
    return {"nnz": np.asarray(S.nnz), "indptr": np.asarray(S.indptr),
            "indices": np.asarray(S.indices), "data": np.asarray(S.data)}


class Reservoir:
    """A seeded uniform sample of ``k`` of the results seen so far."""

    def __init__(self, k: int, seed: int):
        self.k = int(k)
        self.rng = np.random.default_rng([seed, 0x5A])
        self.items: list = []
        self.seen = 0

    def offer(self, item) -> None:
        if len(self.items) < self.k:
            self.items.append(item)
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.k:
                self.items[j] = item
        self.seen += 1


class Loop:
    """Common part: configuration, generator, value sets and sample."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, generator):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.gen = generator
        self.sample = Reservoir(traffic.get("check_sample", 4), seed)
        self.wait_s = 0.0

    def triplets(self) -> int:
        return int(self.cfg["L"])

    def finish(self) -> None:
        """After the window: copy the sample to the host and drop every
        device array of the program."""
        self.sample.items = [(key, _host(S)) for key, S in self.sample.items]

    def close(self) -> None:
        """Stop whatever the loop started; safe to call twice."""

    def _numbers(self, ref, key_vals, got, control: bool) -> dict:
        ss = self.values[key_vals]
        if control:
            got = dict(got, data=check.bf16_data(ref, ss))
        return check.compare(got, ref, ss)


class RefillLoop(Loop):
    def setup(self) -> None:
        from repro.sparse import PlanService

        self.ii, self.jj, self.shape, state = self.gen.generate(
            self.cfg, self.seed)
        self.values = [self.gen.value_set(state, self.seed, k)
                       for k in range(int(self.traffic["value_sets"]))]
        self.svc = PlanService()
        for vals in self.values:   # the first call plans and compiles
            self._assemble(vals)

    def _assemble(self, vals):
        S = self.svc.assemble(self.ii, self.jj, vals, self.shape)
        S.data.block_until_ready()
        return S

    def request(self, r: int):
        k = r % len(self.values)
        self.sample.offer((k, self._assemble(self.values[k])))

    def finish(self) -> None:
        super().finish()
        del self.svc

    def check(self, control: bool) -> list:
        M, N = self.shape
        ref = oracle.StructureReference(self.ii - 1, self.jj - 1, M, N)
        return [self._numbers(ref, k, got, control)
                for k, got in self.sample.items]


class NewLoop(Loop):
    def setup(self) -> None:
        from repro.sparse import fsparse

        self.fsparse = fsparse
        ii, jj, self.shape, state = self.gen.generate(self.cfg, self.seed, 0)
        self.values = [self.gen.value_set(state, self.seed, k)
                       for k in range(int(self.traffic["value_sets"]))]
        for vals in self.values:   # structure 0 warms every program up
            self._assemble(ii, jj, vals)
        self.queue: queue.Queue = queue.Queue(int(self.traffic["queue_depth"]))
        self.stop = threading.Event()
        self.producer = threading.Thread(target=self._produce, daemon=True)
        self.producer.start()
        while not self.queue.full() and self.producer.is_alive():
            self.stop.wait(0.01)

    def _produce(self) -> None:
        k = 1
        while not self.stop.is_set():
            ii, jj, _, _ = self.gen.generate(self.cfg, self.seed, k)
            while not self.stop.is_set():
                try:
                    self.queue.put((k, ii, jj), timeout=0.1)
                    break
                except queue.Full:
                    continue
            k += 1

    def _assemble(self, ii, jj, vals):
        S = self.fsparse(ii, jj, vals, self.shape)
        S.data.block_until_ready()
        return S

    def request(self, r: int):
        import time

        t = time.perf_counter()
        while True:
            try:
                k, ii, jj = self.queue.get(timeout=1.0)
                break
            except queue.Empty:
                if not self.producer.is_alive():
                    raise RuntimeError("the structure producer stopped")
        self.wait_s += time.perf_counter() - t
        v = r % len(self.values)
        self.sample.offer(((k, v), self._assemble(ii, jj, self.values[v])))

    def finish(self) -> None:
        self.close()
        super().finish()

    def close(self) -> None:
        if getattr(self, "producer", None) is None:
            return
        self.stop.set()
        while self.producer.is_alive():
            try:
                self.queue.get_nowait()
            except queue.Empty:
                pass
            self.producer.join(timeout=0.05)
        self.producer = None

    def check(self, control: bool) -> list:
        M, N = self.shape
        out = []
        for (k, v), got in self.sample.items:
            ii, jj, _, _ = self.gen.generate(self.cfg, self.seed, k)
            ref = oracle.StructureReference(ii - 1, jj - 1, M, N)
            out.append(self._numbers(ref, v, got, control))
        return out


LOOPS = {"refill": RefillLoop, "new": NewLoop}
