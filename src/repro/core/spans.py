"""Host spans on the profiler's clock, and named device scopes.

`span(name, acc)` opens a `jax.profiler.TraceAnnotation`, so that a trace
shows the host stage on the same timeline as the device's ops, and adds the
stage's `perf_counter` seconds to `acc[key or name]`: the accumulators that
the program's own timings (`PrepStream.timings`, `DistributedMCE.stats`)
already keep. Keyword ids (`bucket=`, `chunk=`) ride on the trace event.

With the profiler off an annotation costs about a microsecond, so spans go
at stage and chunk granularity only, never inside a per-vertex or per-root
loop.

`scoped(name)` traces a function under `jax.named_scope(name)`: the ops it
emits carry `name` in their HLO `op_name`, so a device trace can give their
time to the engine phase (`engine.refill`, `engine.steal`, `engine.step`).
A scope is metadata only: the compiled program is the same without it.
"""
from __future__ import annotations

import contextlib
import functools
import time
from typing import Callable, Iterator, MutableMapping, Optional

import jax


@contextlib.contextmanager
def span(name: str, acc: MutableMapping[str, float],
         key: Optional[str] = None, **ids) -> Iterator[None]:
    """Trace `name` on the host and add its seconds to `acc[key or name]`."""
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(name, **ids):
        yield
    k = key or name
    acc[k] = acc.get(k, 0.0) + time.perf_counter() - t0


def scoped(name: str) -> Callable:
    """Decorator: trace the function under the device scope `name`."""
    def wrap(fn: Callable) -> Callable:
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap
