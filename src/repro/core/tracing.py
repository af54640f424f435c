"""Named host spans of the CPR path, in the profiler's own trace.

``span(name, **meta)`` is a ``jax.profiler.TraceAnnotation``: when a
``jax.profiler`` session is recording, the span lands in its trace, on the
same clock as the device planes, with ``meta`` as the event's stats.  With
no session running, entering it costs a flag check.  Nothing else records
the spans, and none of them waits for the device.

Spans that belong together share an identifier in their metadata: every
span of one save carries ``save=<n>`` (the manager's save count), every
span of one restore ``failure=<n>``, and the per-table spans of a save
``table=<t>`` besides.  The names:

  cpr.step                  one call of the jitted train step (step_num)
  cpr.save                  a whole ``CPRManager.run_save``
    cpr.save.fetch          tables (and accumulators) copied to the host
    cpr.save.select         the tracker's pick, until its ids are on the host
    cpr.save.gather         the picked rows taken out of the host copies
    cpr.save.store          the store taking in those rows
    cpr.save.small          at a boundary: the small tables and the trainer
    cpr.save.fence          at a boundary: draining the writer
  cpr.restore               one failure handled by ``Emulator.run``
    cpr.restore.fetch       every table and accumulator copied to the host
    cpr.restore.apply       ``CPRManager.on_failure``
    cpr.restore.put         the restored state handed back to the device
"""
from __future__ import annotations

import jax


def span(name: str, **meta):
    """A context manager marking ``name`` (with ``meta``) in the trace."""
    return jax.profiler.TraceAnnotation(name, **meta)


def step_span(step_num: int):
    """The ``cpr.step`` span of one train step, as the profiler's step."""
    return jax.profiler.StepTraceAnnotation("cpr.step", step_num=step_num)
