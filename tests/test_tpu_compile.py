"""The default TPU path compiles for a v5e chip (no chip needed).

The TPU compiler is installed with JAX, so it compiles for a topology
that is described, not attached.  Each test compiles one program of the
served path at deployment size (L = 4e7 triplets, M = N = 8e5) for one
chip of a described ``v5e:2x2`` and checks that the Pallas kernels are
in it (``tpu_custom_call``).  Kernels are compiled with
``interpret=False``: off the chip, ``interpret=None`` means interpret
mode.  The topology is described inside a fixture, never at import, and
only one process may load the TPU library, so these tests stay in this
one file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.radix_sort.ops import plan_digit_passes, radix_sort_pair
from repro.kernels.radix_sort.radix_sort import (
    digit_block_histogram,
    digit_placement,
)
from repro.sparse import tuning
from repro.sparse.pattern import SparsePattern, pattern_from_perm

L = 40_000_000
M = N = 800_000


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2, with JAX's persistent cache off: a compile
    for a described chip cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(one_chip, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _digits():
    """The digit passes the planner runs at L = 4e7 (bins 128 and 49),
    plus the widest digit it may plan (2^11 bins: four 512-bin tiles)."""
    passes = {(p.bits, p.nbins) for p in plan_digit_passes(M, N, L)}
    return sorted(passes | {(11, 2048)})


@pytest.mark.parametrize("bits,nbins", _digits())
@pytest.mark.parametrize("kernel", ["digit_block_histogram",
                                    "digit_placement"])
def test_radix_kernel_compiles_for_v5e(one_chip, kernel, bits, nbins):
    pol = tuning.prior_policy("radix_sort", "tpu")
    tiles = dict(shift=7, bits=bits, nbins=nbins, block_b=pol["block_b"],
                 block_t=pol["block_t"], interpret=False)
    keys = _shape(one_chip, (L,))
    if kernel == "digit_block_histogram":
        text = _compiled_text(lambda k: digit_block_histogram(k, **tiles),
                              keys)
    else:
        nblocks = -(-L // pol["block_b"])
        offsets = _shape(one_chip, (nblocks, nbins))
        text = _compiled_text(
            lambda k, o: digit_placement(k, o, **tiles), keys, offsets)
    assert "tpu_custom_call" in text


def test_radix_planner_compiles_for_v5e(one_chip):
    """The whole ``method="radix"`` plan program (every digit pass and
    the Parts 3-4 tail), as ``plan`` runs it on the chip."""
    def plan_radix(rows, cols):
        perm = radix_sort_pair(rows, cols, M=M, N=N, interpret=False)
        return pattern_from_perm(rows, cols, perm, M=M, N=N, nzmax=L)

    keys = _shape(one_chip, (L,))
    assert "tpu_custom_call" in _compiled_text(plan_radix, keys, keys)


def test_plan_scopes_keep_kernel_names_for_v5e(one_chip, monkeypatch):
    """``plan`` itself, as the chip traces it: its named scopes reach
    the ops' metadata, while the module and the Pallas kernels keep the
    names the benchmark's trace readers match (``jit_plan``,
    ``digit_block_histogram``, ``digit_placement``).  A small odd L, so
    no other test reuses the trace made with the chip's backend."""
    from repro.sparse.pattern import plan

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    keys = _shape(one_chip, (200_003,))
    try:
        text = plan.lower(keys, keys, shape=(5001, 5001),
                          method="radix").compile().as_text()
    finally:
        jax.clear_caches()
    assert text.startswith("HloModule jit_plan,")
    for kernel in ("digit_block_histogram", "digit_placement"):
        assert re.search(rf"%{kernel}(\.\d+)? = ", text), kernel
    for scope in ("plan.sort", "plan.compress"):
        assert f"/{scope}/" in text, scope


def test_served_fill_compiles_for_v5e(one_chip):
    """The executable ``PlanService`` replays per request:
    ``SparsePattern.scatter`` (an XLA gather + scatter-add)."""
    vec = _shape(one_chip, (L,))
    pat = SparsePattern(perm=vec, slot=vec, indices=vec,
                        indptr=_shape(one_chip, (N + 1,)),
                        nnz=_shape(one_chip, ()), srows=vec, scols=vec,
                        shape=(M, N))
    vals = _shape(one_chip, (L,), jnp.float32)
    compiled = jax.jit(lambda p, v: p.scatter(v)).lower(pat, vals).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16e9
