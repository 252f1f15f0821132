"""Named host spans of the served path, on the profiler's clock.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation`` named
``adaptcache/<name>`` carrying ``args``; ``mark(name, **args)`` records
one that ends as it starts. Neither reads a clock nor holds state: the
profiler stamps them in C++ on the clock it also uses for the device's
operations, and only while a profiler session records; otherwise
entering one costs about a microsecond. Nesting on the host thread gives
each span its parent, and spans of one request carry its ``req_id``.

They are host-side: inside a jitted function a ``TraceAnnotation`` runs
at trace time only and records nothing, so device work is named with
``jax.named_scope`` inside the jitted code instead. No argument may feed
simulated state: a traced run is bit-identical to an untraced one.
"""
from __future__ import annotations

import jax

PREFIX = "adaptcache/"


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """Context manager: a host span ``adaptcache/<name>`` with ``args``."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)


def mark(name: str, **args) -> None:
    """A point event: a span entered and left at once."""
    with span(name, **args):
        pass
