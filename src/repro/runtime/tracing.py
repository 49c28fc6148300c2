"""Spans and scopes of the search path, for the JAX profiler.

Two kinds of marks, both named `genie.<step>`:

  * host spans (`span`) are `jax.profiler.TraceAnnotation`s around what a
    host thread does: the front end's dispatch loop, hashing, each part
    launch, the merge, every blocking read of a device result.  With no
    profiler session active a span costs one `TraceMe` check; under
    `jax.profiler.trace` it lands on the calling thread with its stats.
  * device scopes (`scope`) are `jax.named_scope`s inside the jitted
    programs.  They are metadata only: they prefix the `op_name` of every
    HLO instruction traced inside them (so a profiler trace can tie a device
    op to its step) and leave the compiled code and its instruction names as
    they are.

`install_gc_spans` adds a `genie.gc` span around each garbage collection,
so a trace tells a host collection from a slow device step.

Tracing is on exactly when a JAX profiler session is; there is no knob.
docs/SERVING.md lists every span and scope with its stats.
"""
from __future__ import annotations

import functools
import gc

import jax

# host spans
SUBMIT = "genie.submit"      # ServingFrontend.submit; stat request
DISPATCH = "genie.dispatch"  # one coalesced dispatch, the whole call
STACK = "genie.stack"        # concatenate + power-of-two pad of the rows
SEARCH = "genie.search"      # the call into the tenant backend's search
ROUTE = "genie.route"        # coarse routing of a routed plan
PART = "genie.part"          # one part launch of the host loop
WAIT = "genie.wait"          # a blocking device-to-host read
SCATTER = "genie.scatter"    # slicing results and resolving futures
GC = "genie.gc"              # a garbage collection; stats generation, collected
# both a host span and a device scope
HASH = "genie.hash"          # LSH hashing of the query rows
MERGE = "genie.merge"        # merging per-part candidate buffers
# device scopes
MATCH = "genie.match"        # the match kernel (counts [Q, N])
TOPK = "genie.topk"          # a fused match -> count -> local top-k kernel
HIST = "genie.hist"          # the c-PQ count histogram
GATE = "genie.gate"          # the audit threshold from the histogram
COMPACT = "genie.compact"    # the c-PQ candidate compaction
ORDER = "genie.order"        # ordering a candidate buffer into the top-k


def span(name: str, **stats):
    """A host span: `with span(DISPATCH, requests=3) as s: ...`; stats known
    only later go in with `s.set_metadata(...)`."""
    return jax.profiler.TraceAnnotation(name, **stats)


def scope(name: str):
    """A device scope around traced code: `with scope(COMPACT): ...`."""
    return jax.named_scope(name)


def scoped(name: str):
    """Decorator: the whole function runs inside device scope `name` (a
    fresh scope each call: one `jax.named_scope` object is not safe to
    share between threads that trace at once)."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


_gc_span = None              # the span of the collection in progress


def _gc_callback(phase: str, info: dict) -> None:
    # a collection runs start to stop on one thread, holding the GIL, and
    # never nests: one open span at a time
    global _gc_span
    if phase == "start":
        _gc_span = span(GC, generation=int(info.get("generation", -1)))
        _gc_span.__enter__()
    elif _gc_span is not None:
        s, _gc_span = _gc_span, None
        s.set_metadata(collected=int(info.get("collected", 0)))
        s.__exit__(None, None, None)


def install_gc_spans() -> None:
    """Put the `genie.gc` hook in `gc.callbacks`, once per process; with no
    profiler session it costs one `TraceMe` check per collection."""
    if _gc_callback not in gc.callbacks:
        gc.callbacks.append(_gc_callback)
