import os
import sys

import pytest

# tests see ONE device (the dry-run sets its own 512-device flag in a
# subprocess); src/ layout without install.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
# consider_namespace_packages (needed for --doctest-modules over the
# src/repro namespace package) stops pytest from auto-inserting this
# directory, so the shared test helpers (hypothesis_compat) need it back
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

_COMPILE_CACHE_FLAGS = (
    "jax_compilation_cache_dir",
    "jax_persistent_cache_min_compile_time_secs",
    "jax_persistent_cache_min_entry_size_bytes",
)


@pytest.fixture
def private_compile_cache(tmp_path, monkeypatch):
    """Give one test its own JAX compile-cache directory, and put the
    cache settings back afterwards.

    ``PlanService(cache_dir=...)`` turns JAX's persistent cache on for
    the whole process, in ``JAX_COMPILATION_CACHE_DIR`` or else the
    checkout's shared ``.jax_cache``.  Such tests set the variable (and
    the flag, as JAX does when it reads the variable at import) to a
    directory of their own, so test workers running side by side never
    load each other's XLA:CPU executables, and no cache outlives the
    test.
    """
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    saved = {f: getattr(jax.config, f) for f in _COMPILE_CACHE_FLAGS}
    path = str(tmp_path / "jax_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", path)
    jax.config.update("jax_compilation_cache_dir", path)
    yield path
    for flag, value in saved.items():
        jax.config.update(flag, value)
    compilation_cache.reset_cache()
