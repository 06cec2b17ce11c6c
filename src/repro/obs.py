"""Named host spans at the layer boundaries of the train loop and the
serve engine, written into the JAX profiler's trace.

A span is a ``jax.profiler.TraceAnnotation`` named ``repro.<name>``.  The
profiler records it on the host's timeline, on the same clock as the
device's operations and programs, so time in which the device waits can
be put down to what the host was doing then.  Spans nest by time on the
calling thread.  A profiler session is the only switch: without one,
:func:`span` returns a shared null context and builds no metadata.
Metadata are scalars; a callable value is called only while tracing, for
a value worth building only then (a string of request ids).
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

PREFIX = "repro."

TRAIN_NEXT_BATCH = "train.next_batch"
TRAIN_STEP = "train.step"
TRAIN_LOSS_READBACK = "train.loss_readback"
TRAIN_CHECKPOINT = "train.checkpoint"
SERVE_STEP = "serve.step"
SERVE_ADMIT = "serve.admit"
SERVE_PREFILL = "serve.prefill"
SERVE_CAPACITY = "serve.capacity"
SERVE_DECODE = "serve.decode"
SERVE_READBACK = "serve.readback"
SERVE_EMIT = "serve.emit"


class _Off:
    """The span when no profiler session runs: does nothing."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **meta):
        pass


_OFF = _Off()


def span(name: str, **meta):
    """A context for the span ``repro.<name>``; ``set_metadata(**m)`` on
    what it yields adds counters known only at its end."""
    if not TraceAnnotation.is_enabled():
        return _OFF
    return TraceAnnotation(PREFIX + name, **{
        k: v() if callable(v) else v for k, v in meta.items()})
