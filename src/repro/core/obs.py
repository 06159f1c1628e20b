"""Host spans of the scheduler's own layers.

``span(name, **ids)`` is a ``jax.profiler.TraceAnnotation``: under a running
profiler it writes one event into the profiler's host plane, on the same
clock as the device trace, with ``ids`` as the event's stats; with no
profiler running it costs about a microsecond.  The spans, each around one
host step of the served path:

* ``sched.submit``   — ``AdmissionFrontEnd.submit`` (one arrival buffered);
* ``sched.pack``     — building a drain's padded host arrays;
* ``sched.dispatch`` — the jitted drain call (``drain=<seq>``);
* ``sched.fetch``    — fetching that drain's outputs to the host: the wait
  for the device plus the copy (the same ``drain=<seq>``);
* ``sched.mirror``   — folding the outputs into the python mirror
  (queue rows, ``SoAFleet._absorb``, counters);
* ``sched.depart``   — ``SoAFleet.depart`` (one departure transition).

Device-side stages are named with ``jax.named_scope`` where they are traced
(``admission``, ``jax_scheduler``, ``scan_sim``); those names ride in each
HLO op's ``op_name`` metadata and change nothing that runs.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

SUBMIT = "sched.submit"
PACK = "sched.pack"
DISPATCH = "sched.dispatch"
FETCH = "sched.fetch"
MIRROR = "sched.mirror"
DEPART = "sched.depart"


def span(name: str, **ids) -> TraceAnnotation:
    """A host span ``name`` (one of this module's constants) carrying
    ``ids`` (integers, e.g. ``drain=<seq>``) as stats."""
    return TraceAnnotation(name, **ids)
