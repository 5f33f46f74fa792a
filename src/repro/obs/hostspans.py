"""Wall-clock host spans of the control plane, on the profiler's clock.

The flight recorder and telemetry work on the simulation clock: what an
invocation experienced.  This is the wall-clock half: what the control
plane's host code costs while the device waits.  A tracer is any callable
``tracer(name, **stats)`` returning a context manager whose
``set_metadata(**stats)`` adds the counters known only when the work
ends.  The default, ``jax.profiler.TraceAnnotation``, records into a
running ``jax.profiler.trace``: the spans share the clock of the device's
ops and XLA module executions, and their stats come back as each event's
``stats`` in ``jax.profiler.ProfileData``.  Spans live in the profiler's
buffer alone; the tracer keeps no Python containers, and outside a trace
a span records nothing.

Turn it on inside a trace and off again::

    with jax.profiler.trace(log_dir):
        cp.attach_tracer()
        ...                       # admit, run_until
        cp.attach_tracer(None)

Detached (the default), each tap site costs one attribute read and an
``is None`` check.  Attached, a span costs about a microsecond of host
time, two with stats (JAX CPU backend).

The spans, by site (stats in brackets).  ``fdn/complete`` and the
``fdn/drain`` that follows it are siblings inside ``fdn/advance``;
``fdn/drain`` also runs inside ``fdn/enqueue`` at admission:

    fdn/admit             FDNControlPlane.admit [rows, fns: offered]
      fdn/snapshot        the as_snapshot call of the batch paths
      fdn/decide          Policy.fn_decisions
        fdn/decide/gather    the kernel's host arguments (SLO composite)
        fdn/decide/dispatch  packing them into one buffer, its one
                             host-to-device transfer, the kernel's launch
                             and the one copy of the choices back [f, p:
                             the kernel's shape; bytes: the packed buffer]
        fdn/decide/sync      the copy of the choices to the host where the
                             decision returned device arrays (a host no-op
                             on the packed path)
      fdn/enqueue         the sidecar enqueue loop [rows]
        fdn/drain         TargetPlatform._drain [started, materialized]
          fdn/launch      TargetPlatform._launch [rows]
    fdn/advance           SimClock.run_until [events: heap entries popped]
      fdn/complete        completion bookkeeping: metrics fold, callbacks;
                          one row, or every row a burst ends at one instant
                          folded as a group, with the drains its rows
                          trigger [rows: rows completed; grouped: rows that
                          took the group's fold]
        fdn/launch        the rows a folded group's drains start [rows]
      fdn/drain           the drain each row completed alone triggers

The tap sites in ``repro.core`` spell the names out, since ``repro.core``
cannot import ``repro.obs`` (which imports it); ``tests/test_hostspans.py``
pins that a traced run emits exactly the names and stats of ``STATS``.
"""
from __future__ import annotations

ADMIT = "fdn/admit"
SNAPSHOT = "fdn/snapshot"
DECIDE = "fdn/decide"
GATHER = "fdn/decide/gather"
DISPATCH = "fdn/decide/dispatch"
SYNC = "fdn/decide/sync"
ENQUEUE = "fdn/enqueue"
DRAIN = "fdn/drain"
LAUNCH = "fdn/launch"
COMPLETE = "fdn/complete"
ADVANCE = "fdn/advance"

# every span name, and the stats each one carries
STATS = {
    ADMIT: ("rows", "fns"),
    SNAPSHOT: (),
    DECIDE: (),
    GATHER: (),
    DISPATCH: ("f", "p", "bytes"),
    SYNC: (),
    ENQUEUE: ("rows",),
    DRAIN: ("started", "materialized"),
    LAUNCH: ("rows",),
    COMPLETE: ("rows", "grouped"),
    ADVANCE: ("events",),
}
