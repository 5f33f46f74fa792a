"""Wraps around the program's layer calls, installed by the harness.

Two kinds, both on the instances and module attributes of one run:

* capture (every run): what the timed path decided and where it put each
  row, for the comparison that decides ``correct`` (``Capture``);
* spans (``--trace 1`` only): a ``jax.profiler.TraceAnnotation`` host span
  around each layer call, so the profiler's trace shows what the host did
  while the device sat idle.

A wrapped name that no longer exists raises ``LayerMissing``: a renamed
layer makes the run fail, it is never read as zero.
"""
from __future__ import annotations

import functools
import importlib
import inspect
from typing import Callable, List

import numpy as np

# span names, outermost first; fdnbench/window encloses one traced window
WINDOW, ADMIT, SNAPSHOT, DECIDE, ENQUEUE, ADVANCE = (
    "fdnbench/window", "fdnbench/admit", "fdnbench/snapshot",
    "fdnbench/decide", "fdnbench/enqueue", "fdnbench/advance")

KERNEL_MODULE = "repro.kernels.policy_score"
KERNEL_NAME = "fused_composite_decide"
SNAPSHOT_MODULE = "repro.core.control_plane"
SNAPSHOT_NAME = "as_snapshot"


class LayerMissing(RuntimeError):
    pass


class _Buffers:
    """Named NumPy columns grown by doubling."""

    def __init__(self, n: int, **cols):
        self.n = 0
        self._spec = cols
        for name, (dtype, shape, fill) in cols.items():
            setattr(self, name, np.full((n,) + shape, fill, dtype))

    def reserve(self, k: int) -> int:
        """Make room for ``k`` more rows; returns the first one."""
        first = self.n
        cap = getattr(self, next(iter(self._spec))).shape[0]
        if first + k > cap:
            new = max(2 * cap, first + k)
            for name, (dtype, shape, fill) in self._spec.items():
                old = getattr(self, name)
                grown = np.full((new,) + shape, fill, dtype)
                grown[:first] = old[:first]
                setattr(self, name, grown)
        self.n = first + k
        return first


class Capture:
    """What the timed path decided, the estimator columns and utilization
    each decision read, how many completions the run had recorded by then
    (``d.done``), and where it enqueued each row, kept in NumPy buffers:
    the capture leaves no Python container behind per batch, so it does
    not feed the garbage collector whose pauses the window measures.  ``batch`` is set only inside the window's ``request_batch``
    calls, so warm-up, hedge duplicates and redelivery fired from the
    event loop are not counted as admissions."""

    EST = ("ewma_v", "ewma_n", "resp_h2", "resp_n")

    def __init__(self, fn_names, plat_names, kernel):
        params = list(inspect.signature(kernel).parameters)
        self._est_pos = [params.index(k) for k in self.EST]
        self.fn_names, self.plat_names = list(fn_names), list(plat_names)
        self._fn = {n: i for i, n in enumerate(self.fn_names)}
        self._plat = {n: j for j, n in enumerate(self.plat_names)}
        p = len(self.plat_names)
        self.b = _Buffers(256, row0=(np.int64, (), 0),
                          rows=(np.int64, (), 0),
                          calls=(np.int32, (), 0), kcalls=(np.int32, (), 0),
                          stateful=(bool, (), False))
        self.r = _Buffers(4096, fn=(np.int16, (), -1),
                          enq=(np.int16, (), 0), plat=(np.int16, (), -1))
        self.d = _Buffers(1024, batch=(np.int64, (), -1),
                          done=(np.int64, (), 0),
                          fn=(np.int16, (), -1), idx=(np.int16, (), -1),
                          ok=(bool, (), False),
                          ewma_v=(np.float64, (p,), 0.0),
                          ewma_n=(np.int64, (p,), 0),
                          resp_h2=(np.float64, (p,), 0.0),
                          resp_n=(np.int64, (p,), 0),
                          cpu=(np.float64, (p,), 0.0),
                          mem=(np.float64, (p,), 0.0),
                          present=(bool, (p,), False))
        self.foreign_rows = 0
        self.sink = None       # the run's completion record
        self.batch = -1
        self._obj = None
        self._row_of = None
        self._est = None

    # ------------------------------------------------------- harness side --
    def begin(self, batch) -> None:
        b = self.b.reserve(1)
        r0 = self.r.reserve(batch.n)
        self.b.row0[b], self.b.rows[b] = r0, batch.n
        fmap = np.array([self._fn[s.name] for s in batch.specs], np.int16)
        self.r.fn[r0:r0 + batch.n] = fmap[batch.fn_idx]
        self.batch, self._obj, self._row_of = b, batch, None

    def end(self) -> None:
        self.batch, self._obj, self._row_of = -1, None, None

    # ------------------------------------------------------- program side --
    def kernel_call(self, args, kw) -> None:
        self.b.kcalls[self.batch] += 1
        self._est = [kw[k] if k in kw else args[i]
                     for k, i in zip(self.EST, self._est_pos)]

    def decisions(self, fns, snap, res) -> None:
        b = self.batch
        self.b.calls[b] += 1
        if res is None:
            self.b.stateful[b] = True
            return
        est, self._est = self._est, None
        f = len(fns)
        k = self.d.reserve(f)
        d = self.d
        cols = np.array([self._plat[n] for n in snap.names], np.int64)
        d.batch[k:k + f] = b
        d.done[k:k + f] = self.sink.completed
        d.fn[k:k + f] = [self._fn[fn.name] for fn in fns]
        d.idx[k:k + f] = cols[np.asarray(res[0])]
        d.ok[k:k + f] = np.asarray(res[1])
        d.present[k:k + f, cols] = True
        d.cpu[k:k + f, cols] = snap.cpu_util
        d.mem[k:k + f, cols] = snap.mem_util
        if est is not None:
            for name, arr in zip(self.EST, est):
                getattr(d, name)[k:k + f, cols] = arr

    def enqueued(self, pname: str, rows) -> None:
        g = self.b.row0[self.batch] + rows
        np.add.at(self.r.enq, g, 1)
        self.r.plat[g] = self._plat.get(pname, -1)

    def enqueued_objects(self, pname: str, invs) -> None:
        if self._row_of is None:
            self._row_of = {id(v): i for i, v in
                            enumerate(self._obj.to_invocations())}
        rows = [self._row_of.get(id(v), -1) for v in invs]
        self.foreign_rows += sum(1 for i in rows if i < 0)
        self.enqueued(pname, np.array([i for i in rows if i >= 0],
                                      np.int64))


def _need(obj, name: str):
    try:
        return getattr(obj, name)
    except AttributeError:
        raise LayerMissing(f"{obj!r} has no attribute {name!r}: the "
                           "layer the benchmark wraps is gone") from None


class Wraps:
    """Installs wraps and takes them all off again (``remove``)."""

    def __init__(self):
        self._undo: List[Callable[[], None]] = []

    def module_attr(self, module: str, name: str, make):
        mod = importlib.import_module(module)
        orig = _need(mod, name)
        setattr(mod, name, make(orig))
        self._undo.append(lambda: setattr(mod, name, orig))

    def instance_attr(self, obj, name: str, make):
        orig = _need(obj, name)
        own = vars(obj)
        had, prev = name in own, own.get(name)
        setattr(obj, name, make(orig))
        self._undo.append(lambda: setattr(obj, name, prev) if had
                          else delattr(obj, name))

    def remove(self):
        while self._undo:
            self._undo.pop()()


def install_capture(wraps: Wraps, dep, cap: Capture) -> None:
    def kernel(orig):
        @functools.wraps(orig)
        def f(*args, **kw):
            if cap.batch >= 0:
                cap.kernel_call(args, kw)
            return orig(*args, **kw)
        return f

    def decisions(orig):
        def f(fns, snap, n=None):
            res = orig(fns, snap, n=n)
            if cap.batch >= 0:
                cap.decisions(fns, snap, res)
            return res
        return f

    def columns(pname):
        def make(orig):
            def f(batch, idxs):
                if cap.batch >= 0:
                    cap.enqueued(pname, idxs)
                return orig(batch, idxs)
            return f
        return make

    def objects(pname, single):
        def make(orig):
            def f(invs):
                if cap.batch >= 0:
                    cap.enqueued_objects(pname, (invs,) if single else invs)
                return orig(invs)
            return f
        return make

    cap.sink = dep.sink
    wraps.module_attr(KERNEL_MODULE, KERNEL_NAME, kernel)
    wraps.instance_attr(dep.cp.policy, "fn_decisions", decisions)
    for pname, sc in dep.cp.sidecars.items():
        wraps.instance_attr(sc, "admit_columns", columns(pname))
        wraps.instance_attr(sc, "admit_many", objects(pname, False))
        wraps.instance_attr(sc, "admit", objects(pname, True))


def install_spans(wraps: Wraps, dep) -> None:
    from jax.profiler import TraceAnnotation

    def span(name):
        def make(orig):
            def f(*args, **kw):
                with TraceAnnotation(name):
                    return orig(*args, **kw)
            return f
        return make

    wraps.instance_attr(dep.gateway, "request_batch", span(ADMIT))
    wraps.module_attr(SNAPSHOT_MODULE, SNAPSHOT_NAME, span(SNAPSHOT))
    wraps.instance_attr(dep.cp.policy, "fn_decisions", span(DECIDE))
    for sc in dep.cp.sidecars.values():
        for name in ("admit_columns", "admit_many", "admit"):
            wraps.instance_attr(sc, name, span(ENQUEUE))
    wraps.instance_attr(dep.cp.clock, "run_until", span(ADVANCE))
