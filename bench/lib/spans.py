"""The program's spans, kept and put beside the device trace.

``AnnotatingTracer`` is the program's own ``repro.obs.SpanTracer`` with
one addition: while the profiler runs, every span the program opens is
also a ``jax.profiler.TraceAnnotation`` named ``program:<span>``, so the
device trace's idle gaps can be named by what the host was doing.
``on_span`` lets the harness act at span boundaries (start and stop the
profiler from the thread that runs the program).
"""
from __future__ import annotations

import contextlib

import jax


def tracer_class():
    from repro.obs import SpanTracer

    class AnnotatingTracer(SpanTracer):
        def __init__(self, on_span=None, capacity: int = 2_000_000):
            super().__init__(capacity=capacity)
            self.on_span = on_span
            self.annotate = False

        @contextlib.contextmanager
        def span(self, name, track=1, args=None):
            if self.on_span is not None:
                self.on_span(name)
            inner = super().span(name, track, args)
            if self.annotate:
                with jax.profiler.TraceAnnotation(f"program:{name}"), inner:
                    yield inner
            else:
                with inner:
                    yield inner

    return AnnotatingTracer


def harness_span(name: str):
    """A host span of the harness's own, named ``bench:<name>``."""
    return jax.profiler.TraceAnnotation(f"bench:{name}")


def profile_options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    return opts


def span_durations(tracer, name: str) -> list:
    """Durations in seconds of the program's spans called ``name``."""
    return [ev["dur"] / 1e6 for ev in tracer.events()
            if ev.get("ph") == "X" and ev.get("name") == name]
