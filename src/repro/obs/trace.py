"""Program spans on the profiler's clock.

Every span is a ``jax.profiler.TraceAnnotation`` with a stable, bare
name, so a profiler session (``jax.profiler.trace`` — the launchers'
``--trace DIR``, or the benchmark's traced run) records the program's
host phases on the same clock as the device operations. There is one
tracing mechanism: the profiler. Device time is read from its device
planes, never modelled from host clocks.

Span names (the benchmark's readers match them exactly):

  * ``join`` — one ``JoinEngine.join`` / ``submit`` call (and one
    pipelined ``submit_many`` group); ``join/prepare`` (query upload,
    fingerprint, index and cascade lookup) and ``join/ood`` (the OOD
    prediction of ``es_mi_adapt``) inside it;
  * ``wave/launch``, ``wave/band``, ``wave/feedback``, ``wave/assemble``,
    ``wave/cache_update``, ``wave/refinalize`` — the wave pipeline's
    host phases, and ``wave/fetch`` around each blocking ``device_get``
    inside band, feedback and assemble;
  * ``index/build`` — the index builds;
  * ``serve_join/round``, ``serve_join/tenant_batch``,
    ``serve_join/warmup`` — the join service.

Spans always open: with no profiler session a span costs one TraceMe
check and records nothing. Attributes travel as event stats. Compute an
expensive one only under ``recording()``. Spans opened inside one
``call_span`` carry its ``call=<n>``, and wave spans carry ``wave=<k>``.
Tracing never touches the data path, so traced and untraced runs emit
identical pair sets and counters (asserted in tests/test_obs.py).
"""
from __future__ import annotations

import contextlib
import contextvars
import itertools

from jax.profiler import TraceAnnotation

__all__ = ["span", "call_span", "recording", "profile"]

_calls = itertools.count(1)
# the call whose spans are open (0 outside any): context-local, so
# threads and tasks each see their own
_call = contextvars.ContextVar("call", default=0)


def recording() -> bool:
    """Whether a profiler session is recording spans right now."""
    return TraceAnnotation.is_enabled()


def span(name: str, **attrs) -> TraceAnnotation:
    """A program span; the attributes (and the open call's id) are
    attached only while a profiler session records."""
    if TraceAnnotation.is_enabled():
        return TraceAnnotation(name, call=_call.get(), **attrs)
    return TraceAnnotation(name)


@contextlib.contextmanager
def call_span(name: str = "join", **attrs):
    """One top-level call: a fresh ``call`` id for every span inside.
    A context manager, or a decorator of the call's function."""
    token = _call.set(next(_calls))
    try:
        with span(name, **attrs):
            yield _call.get()
    finally:
        _call.reset(token)


def profile(log_dir: str):
    """A profiler session that writes program spans and device ops on
    one clock under ``log_dir`` (an ``.xplane.pb`` and a Perfetto
    ``.trace.json.gz``). The Python call tracer stays off: the spans
    name the program's phases."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return jax.profiler.trace(log_dir, create_perfetto_trace=True,
                              profiler_options=opts)
