"""Faults planted in the program under test, to show that ``correct``
catches them.  Each is a context manager that patches a class or module
attribute of the program for the duration of a run and puts it back.

* ``stuck``: the gateway returns without admitting anything: the step
  returns its state unchanged.
* ``half-batch``: the gateway admits only the first half of each batch;
  the rest is never decided.
* ``alter-answer``: the decision kernel's choice is moved to the next
  platform where it is produced.
* ``misroute``: admission hands each group to the sidecar of another
  platform than the one decided.
* ``drop-observation``: the performance model folds only every other
  completion into its estimators.

The window has no exchange between chips (a decision runs on one
device), so that fault has no place here.
"""
from __future__ import annotations

import contextlib
import functools


@contextlib.contextmanager
def _patched(owner, name, make):
    orig = getattr(owner, name)
    setattr(owner, name, make(orig))
    try:
        yield
    finally:
        setattr(owner, name, orig)


def stuck():
    from repro.core.gateway import Gateway

    def make(orig):
        @functools.wraps(orig)
        def request_batch(self, invs, *a, **kw):
            return 0
        return request_batch
    return _patched(Gateway, "request_batch", make)


def half_batch():
    from repro.core.gateway import Gateway

    def make(orig):
        @functools.wraps(orig)
        def request_batch(self, invs, *a, **kw):
            return orig(self, invs.view(0, invs.n // 2), *a, **kw)
        return request_batch
    return _patched(Gateway, "request_batch", make)


def alter_answer():
    from repro.kernels import policy_score

    def make(orig):
        @functools.wraps(orig)
        def decide(*args, **kw):
            idx, ok = orig(*args, **kw)
            return (idx + 1) % args[0].shape[1], ok
        return decide
    return _patched(policy_score, "fused_composite_decide", make)


def misroute():
    from repro.core.control_plane import FDNControlPlane

    def make(orig):
        @functools.wraps(orig)
        def admit(self, req):
            names = list(self.sidecars)
            saved = dict(self.sidecars)
            for a, b in zip(names, names[1:] + names[:1]):
                self.sidecars[a] = saved[b]
            try:
                return orig(self, req)
            finally:
                self.sidecars.update(saved)
        return admit
    return _patched(FDNControlPlane, "admit", make)


def drop_observation():
    from repro.core.behavioral import FunctionPerformanceModel

    def make(orig):
        seen = [0]

        @functools.wraps(orig)
        def observe(self, inv):
            seen[0] += 1
            if seen[0] % 2:
                return orig(self, inv)
        return observe
    return _patched(FunctionPerformanceModel, "observe", make)


FAULTS = {"stuck": stuck, "half-batch": half_batch,
          "alter-answer": alter_answer, "misroute": misroute,
          "drop-observation": drop_observation}
