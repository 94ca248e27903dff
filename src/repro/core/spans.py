"""Names of the host spans on the served request path.

Every stage of a request runs inside ``span(NAME, **stats)``, which is
:class:`jax.profiler.TraceAnnotation`: while a profiler trace is being
taken (``jax.profiler.trace(dir)``) each span lands in it on the same
clock as the device's operations, with its keyword arguments as event
stats; with no trace running a span costs about a microsecond.  Spans
nest on the caller's thread, and the outermost one of a request carries
its stats.  A span wraps host code only, never a function that
``jax.jit`` traces, and adds no synchronisation: a span around an
asynchronous dispatch measures the dispatch.

The device side has stable ``jax.named_scope`` names instead
(``plan.sort``, ``plan.compress``, ``fill``, ``merge``, ``spmv``,
``multiply``), which reach each compiled op's metadata.
"""
from __future__ import annotations

import jax

#: outer spans, one per request method of ``PlanService`` (stats:
#: ``request``, the service's request sequence number) and of
#: ``fsparse``
ASSEMBLE = "sparse.assemble"
ASSEMBLE_MANY = "sparse.assemble_many"
UPDATE_STRUCTURE = "sparse.update_structure"
MULTIPLY = "sparse.multiply"
SPMV = "sparse.spmv"
FSPARSE = "sparse.fsparse"

#: stage spans, in the order a cold request crosses them; a warm one
#: (index vectors the plan LRU already holds) crosses only PLAN_KEY,
#: UPLOAD (values only), EXEC_CACHE and FILL
EXPAND = "sparse.expand"          # fsparse index expansion to float64
VALIDATE = "sparse.validate"      # index checks and int32 casts
UPLOAD = "sparse.upload"          # zero-offset, float32, copies (bytes)
PLAN_KEY = "sparse.plan_key"      # structure key, or the warm compare
#                                   of the raw indices (bytes, hit)
PLAN_CACHE = "sparse.plan_cache"  # plan LRU lookup
PLAN = "sparse.plan"              # the symbolic phase, when it runs
EXEC_CACHE = "sparse.exec_cache"  # executable LRU lookup (hit)
COMPILE = "sparse.compile"        # lowering and compiling, when it runs
FILL = "sparse.fill"              # dispatch of the numeric fill

span = jax.profiler.TraceAnnotation
