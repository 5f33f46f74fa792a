"""One run of one cell: load, set up, measure, check, report.

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``fdnbench/configs/<config>.json``) and a traffic mix
(``fdnbench/traffic/<traffic>.json``); its limits are in
``fdnbench/checks/<cell>.json`` and each per-layer metric is read by
``fdnbench/metrics/<name before the first dot>.py``.  Nothing here names a
cell, a configuration, a traffic mix or a per-layer metric.

The timed window drives the entry users call: ``Gateway.request_batch``
-> ``FDNControlPlane.admit`` -> ``Policy.fn_decisions`` (the fused
decision on the device) -> sidecar and platform enqueue and drain.
Between batches ``SimClock.run_until`` runs the event loop, so
completions, the drains they trigger, the sink and the autoscaler's ticks
all happen inside the window.
"""
from __future__ import annotations

import gc
import glob
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

from fdnbench import check, deployment, kernels, layers, stats, tracereduce
from fdnbench.reference import cascade
from fdnbench.traffic import Traffic

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "fdnbench")


class NoChip(RuntimeError):
    pass


def _load(path: str) -> Dict:
    with open(path) as fh:
        return json.load(fh)


@dataclass
class Cell:
    name: str
    spec: Dict
    config: Dict
    mix: Dict
    limits: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, mix_override: Optional[Dict] = None) -> Cell:
    bench = _load(os.path.join(ROOT, "BENCHMARK.json"))
    spec = next((w for w in bench["workloads"] if w["name"] == name), None)
    if spec is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    mix = _load(os.path.join(HERE, "traffic", spec["traffic"] + ".json"))
    mix.update(mix_override or {})
    return Cell(
        name, spec,
        _load(os.path.join(HERE, "configs", spec["config"] + ".json")),
        mix, _load(os.path.join(HERE, "checks", name + ".json")),
        [m for m in bench["end_to_end"] if _applies(m, name)],
        [m for m in bench["per_layer"] if _applies(m, name)])


def reader(metric_name: str):
    base = metric_name.split(".")[0]
    path = os.path.join(HERE, "metrics", base + ".py")
    spec = importlib.util.spec_from_file_location(
        "fdnbench.metrics." + base, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileCounter:
    """Counts programs handed to XLA (``n``: compiled, or loaded from the
    persistent cache) and those the persistent cache served (``hits``)."""

    def __init__(self):
        import jax
        self.n = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


_COMPILES: Optional[CompileCounter] = None


def use_compile_cache() -> str:
    """JAX's persistent compilation cache: where JAX_COMPILATION_CACHE_DIR
    says, else at a fixed path inside the checkout (the path is part of
    the cache's key).  Every program is cached, however fast it compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def devices(chips: int, cpu: bool):
    import jax
    devs = jax.devices()
    if cpu:
        return devs[:1]
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(f"this cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


class GcPauses:
    """Collector pauses inside the window, by generation (reported on an
    earlier line: a full collection can hold a batch for tens of ms)."""

    def __init__(self):
        self.t0 = 0.0
        self.count = [0, 0, 0]
        self.total = [0.0, 0.0, 0.0]
        self.worst = [0.0, 0.0, 0.0]

    def __call__(self, phase, info):
        if phase == "start":
            self.t0 = time.perf_counter()
            return
        g, d = info["generation"], time.perf_counter() - self.t0
        self.count[g] += 1
        self.total[g] += d
        self.worst[g] = max(self.worst[g], d)

    def report(self):
        return {"gc_count": self.count,
                "gc_total_ms": [1e3 * t for t in self.total],
                "gc_worst_ms": [1e3 * t for t in self.worst]}


def _emit(tag: str, **kw) -> None:
    print(json.dumps({"fdnbench": tag, **kw}), file=sys.stderr,
          flush=True)


# ------------------------------------------------------------ windows ----

def _admit(dep, cap, batch) -> None:
    cap.begin(batch)
    try:
        dep.gateway.request_batch(batch)
    finally:
        cap.end()


def drive(dep, traffic: Traffic, window_s: float, sim_s: float,
          every_s: Optional[float] = None, sample=None) -> int:
    """Drive the traffic from sim time 0 to ``sim_s``, as fast as the host
    allows, in admission windows of ``window_s``: advance the clock to a
    window's close, then admit its arrivals.  ``sample(dep)`` is called at
    every multiple of ``every_s``.  Returns the rows admitted."""
    clock = dep.cp.clock
    per = max(int(round(every_s / window_s)), 1) if every_s else 0
    k, rows = 0, 0
    while True:
        t_end = min((k + 1) * window_s, sim_s)
        clock.run_until(t_end)
        b = traffic.take(t_end)
        if b.n:
            dep.gateway.request_batch(b)
            rows += b.n
        k += 1
        if per and k % per == 0:
            sample(dep)
        if t_end >= sim_s:
            return rows


def prerun(dep, traffic: Traffic) -> int:
    """Set-up: drive the same traffic for the mix's warm-up span of sim
    time, in its warm-up windows, so platforms and estimators reach steady
    state and every program the window runs has compiled."""
    return drive(dep, traffic, traffic.warmup_window_s, traffic.warmup_sim_s)


def closed_window(dep, traffic: Traffic, seconds: float, cap) -> Dict:
    """Bulk replay: each admission window goes in as soon as the previous
    ``request_batch`` has returned and the clock is at its close."""
    clock, w = dep.cp.clock, traffic.window_s
    t_sim0 = clock.now()
    rows = batches = k = 0
    gen_s = 0.0
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while True:
        t_end = t_sim0 + (k + 1) * w
        clock.run_until(t_end)
        g0 = time.perf_counter()
        b = traffic.take(t_end)
        gen_s += time.perf_counter() - g0
        if b.n:
            _admit(dep, cap, b)
            rows += b.n
        k += 1
        batches += 1
        if time.perf_counter() >= deadline:
            break
    elapsed = time.perf_counter() - t0
    return {"rows": rows, "batches": batches, "elapsed_s": elapsed,
            "sim_s": clock.now() - t_sim0, "generator_s": gen_s}


def open_window(dep, traffic: Traffic, seconds: float, cap) -> Dict:
    """Gateway: sim time runs 1:1 with wall time.  Batch k holds the
    arrivals of sim window k and is due at that window's close; it goes in
    when due, or late if the previous batch overran.  Its rows' admission
    latency is when ``request_batch`` returned minus the due time."""
    clock, w = dep.cp.clock, traffic.window_s
    n = int(round(seconds / w))
    lat, late = np.empty(n), np.empty(n)
    rows = np.zeros(n, np.int64)
    t_sim0 = clock.now()
    t0 = time.perf_counter()
    for k in range(n):
        due = t0 + (k + 1) * w
        wait = due - time.perf_counter()
        if wait > 0.002:
            time.sleep(wait - 0.001)
        while time.perf_counter() < due:
            pass
        late[k] = time.perf_counter() - due
        t_end = t_sim0 + (k + 1) * w
        clock.run_until(t_end)
        b = traffic.take(t_end)
        if b.n:
            _admit(dep, cap, b)
        lat[k] = time.perf_counter() - due
        rows[k] = b.n
    elapsed = time.perf_counter() - t0
    q = max(n // 4, 1)
    slow = late > 0.05
    stalls = int(slow[0]) + int((slow[1:] & ~slow[:-1]).sum())
    return {"rows": int(rows.sum()), "batches": n, "elapsed_s": elapsed,
            "sim_s": clock.now() - t_sim0, "latency_s": lat,
            "rows_per_batch": rows,
            "late_mean_ms": 1e3 * float(late.mean()),
            "late_p99_ms": 1e3 * float(np.percentile(late, 99)),
            "late_max_ms": 1e3 * float(late.max()),
            "stalls_over_50ms": stalls,
            "late_first_quarter_ms": 1e3 * float(late[:q].mean()),
            "late_last_quarter_ms": 1e3 * float(late[-q:].mean())}


# ---------------------------------------------------------------- run ----

@dataclass
class Outcome:
    result: Dict
    cell: Cell
    capture: layers.Capture
    reference: cascade.Decision
    estimates: object          # reference.estimators.Estimates
    fleet: cascade.Fleet
    window: Dict


def _sim_outcomes(dep, done0: int) -> Dict:
    cols = dep.sink.completion_columns()
    rt = (cols["end"] - cols["arrival"])[done0:]
    fid = cols["fn"][done0:]
    slo = np.zeros(max(cols["fn_ids"].values(), default=-1) + 1)
    for name, i in cols["fn_ids"].items():
        slo[i] = cols["fn_specs"][name].slo.p90_response_s
    plat = np.bincount(cols["platform"][done0:],
                       minlength=len(cols["platform_ids"]))
    return {"completed": int(rt.size),
            "slo_violation_share": float((rt > slo[fid]).mean())
            if rt.size else None,
            "completed_by_platform": {
                p: int(plat[i]) for p, i in cols["platform_ids"].items()},
            "queued_rows": deployment.queued_rows(dep.cp)}


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             cpu: bool = False, t_start: Optional[float] = None,
             mix_override: Optional[Dict] = None,
             trace_out: Optional[str] = None) -> Outcome:
    global _COMPILES
    t_start = time.perf_counter() if t_start is None else t_start
    cell = load_cell(name, mix_override)
    import jax
    from repro.core import scheduler
    from repro.kernels import policy_score
    if _COMPILES is None:
        _COMPILES = CompileCounter()
    compiles = _COMPILES
    devs = devices(int(cell.spec["chips"]), cpu)
    kernel = getattr(policy_score, layers.KERNEL_NAME)
    backend = scheduler.get_score_backend()
    cap = layers.Capture([f["name"] for f in cell.config["functions"]],
                         [p["name"] for p in cell.config["platforms"]],
                         kernel)
    wraps = layers.Wraps()
    tmp = None
    try:
        dep = deployment.build(cell.config,
                               cell.mix.get("control_plane", {}))
        traffic = Traffic(cell.mix, cell.config, dep.specs, seed)
        layers.install_capture(wraps, dep, cap)
        warm_rows = prerun(dep, traffic)
        # a window can carry any subset of the functions: one decision of
        # each size, so none compiles in the window
        from repro.core.scheduler import as_snapshot
        snap = as_snapshot(dep.cp.alive_platforms())
        for f in range(1, len(dep.specs) + 1):
            dep.cp.policy.fn_decisions(dep.specs[:f], snap, n=f)
        done0 = dep.sink.completed
        if trace:
            layers.install_spans(wraps, dep)
            tmp = tempfile.mkdtemp(prefix="fdnbench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tmp, profiler_options=opts)
        n_comp0 = compiles.n
        setup_s = time.perf_counter() - t_start
        win = closed_window if traffic.loop == "closed" else open_window
        pauses = GcPauses()
        gc.callbacks.append(pauses)
        try:
            with jax.profiler.TraceAnnotation(layers.WINDOW):
                window = win(dep, traffic, seconds, cap)
        finally:
            gc.callbacks.remove(pauses)
        window.update(pauses.report())
        if trace:
            jax.profiler.stop_trace()
        window["compiles_in_window"] = compiles.n - n_comp0
    finally:
        wraps.remove()
        scheduler.set_score_backend(backend)
    peak = 0
    for d in devs:
        ms = d.memory_stats() or {}
        peak = max(peak, int(ms.get("peak_bytes_in_use", 0)))
    sim = _sim_outcomes(dep, done0)

    fleet = cascade.Fleet(cell.config)
    ref = check.reference(cap, fleet)
    est = check.estimates(cap, fleet, dep.sink.completion_columns())
    numbers = dict(check.admission_numbers(cap))
    numbers.update(check.decision_numbers(cap, ref))
    numbers.update(check.estimator_numbers(cap, fleet, est))
    checks = {k: {"value": numbers[k], "limit": lim["limit"]}
              for k, lim in cell.limits.items()}
    correct = all(v["value"] <= v["limit"] for v in checks.values())

    metrics: Dict[str, Dict] = {}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    breakdown = None
    if trace:
        path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                         recursive=True)
        if len(path) != 1:
            raise tracereduce.TraceError(f"expected one trace, found "
                                         f"{path}")
        if trace_out:
            os.makedirs(trace_out, exist_ok=True)
            shutil.copy(path[0], os.path.join(trace_out, f"{name}.xplane.pb"))
        events = tracereduce.load_xplane(path[0])
        summary = tracereduce.summarize(events, window["batches"])
        # a later metric's reader may take what it needs from the raw
        # spans, device ops and module executions
        summary["events"] = events
        shutil.rmtree(tmp, ignore_errors=True)
        nf = np.bincount(cap.d.batch[:cap.d.n], minlength=cap.b.n)
        p = len(cap.plat_names)
        shapes = [(int(f), p) for f in nf[nf > 0]]
        summary["kernel_roofline_pct"] = kernels.roofline_pct(
            shapes, summary["kernel_s"], summary["kernel_calls"],
            kernels.peaks(devs[0].device_kind)) if not cpu else None
        for m in cell.per_layer:
            v = reader(m["name"])(summary)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=summary["busy_s"],
                      window_s=summary["window_s"])
        breakdown = summary["breakdown"]
        _emit("trace", kernel_calls=summary["kernel_calls"],
              kernel_calls_captured=len(shapes),
              kernel_s=summary["kernel_s"], layer_ms=summary["layer_ms"])
    else:
        for m in cell.end_to_end:
            v = _end_to_end(m["name"], setup_s, window)
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    _emit("setup", setup_s=setup_s, warmup_rows=warm_rows,
          compiles_total=compiles.n, cache_hits=compiles.hits,
          traffic=traffic.describe())
    _emit("window", **{k: v for k, v in window.items()
                       if not isinstance(v, np.ndarray)})
    _emit("simulated", **sim)
    result = {"correct": bool(correct), "attempted": window["rows"],
              "failed": check.rejected_rows(cap),
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if cpu:
        result["rehearsal"] = True
    result["checks"] = checks
    return Outcome(result, cell, cap, ref, est, fleet, window)


def _end_to_end(name: str, setup_s: float, window: Dict) -> float:
    if name == "setup_s":
        return setup_s
    if name == "decisions_per_s":
        return stats.rate(window["rows"], window["elapsed_s"])
    if name == "admit_p50_ms":
        return 1e3 * stats.row_percentile(window["latency_s"],
                                          window["rows_per_batch"], 50)
    raise KeyError(f"the harness does not measure {name!r}")


def print_checks(result: Dict) -> None:
    """The numbers compared, each beside its limit: the last lines on
    standard error."""
    for k, v in result["checks"].items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
