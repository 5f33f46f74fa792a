"""Chip smoke test: drive the FDN admission path once on one TPU chip.

    python chip_smoke.py              # on a machine with a TPU
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse

Everything runs in this one process, through the entry points a user
calls, with the score backend pinned to ``jax`` so every admission
decision runs the compiled cascade on the device:

  a. streaming replay of a ~10^6-arrival burst hour
     (``scale/million-burst``'s size) through ``stream_replay``;
  b. columnar ``submit_batch`` admission (40 000 invocations in batches
     of 2 048), then jax-vs-NumPy decision parity;
  c. a few hundred closed-loop requests through ``Gateway.request`` over
     the TPU platform fleet, hedging and predictive prewarm on;
  d. the ``smoke/tiny`` and ``qos/burst-storm-drr`` scenario reports,
     diffed against ``benchmarks/golden/``;
  e. the packed device decision (``fused_composite_decide``) equal to
     the NumPy ``SLOCompositePolicy`` cascade.

Each phase prints one JSON line naming the device, with its counts,
checks and wall seconds (compilation and warm-up reported apart).  A
failed check raises and the script exits non-zero.  The last line is
``{"ok": true, "device": {...}}`` and appears only when every phase
passed on a TPU.  Without a TPU the script stops before any phase.
``--rehearse`` runs the phases on whatever device JAX has, phase b at
a tenth of its size, and never prints ``"ok"``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

GOLDEN_SCENARIOS = ("smoke/tiny", "qos/burst-storm-drr")
CASCADE_SHAPES = ((4, 5), (64, 1024))     # paper fleet; pod-scale registry
SERVE_DURATION_S = 30.0


class SmokeFailure(AssertionError):
    pass


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def emit(phase: str, device: dict, **fields) -> None:
    print(json.dumps({"phase": phase, "device": device, **fields}),
          flush=True)


class CompileCounter:
    """Counts XLA backend compilations (persistent-cache hits excluded)."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def pin_jax_backend() -> None:
    from repro.core import scheduler as sched
    sched.set_score_backend("jax")


def phase_streaming_replay(device, compiles):
    from benchmarks import bench_streaming_replay as bsr
    from benchmarks.fdn_common import build_fdn
    from repro.inspector.streaming import stream_replay

    pin_jax_backend()
    t0 = time.perf_counter()
    cp, _gw, fns = build_fdn(analytic=True)
    cp.kb.log_decisions = False
    warm = stream_replay(cp, fns, bsr._trace(60, 20_000),
                         chunk_minutes=bsr.CHUNK_MINUTES, seed=7)
    require(warm.rejected == 0, "warm-up replay rejected arrivals")
    warmup_s = time.perf_counter() - t0

    pin_jax_backend()
    n0 = compiles.n
    res: dict = {}
    _rows, failures = bsr.run_bench(smoke=True, rss_limit_mb=float("inf"),
                                    results_out=res)
    require(not failures, f"streaming replay: {failures}")
    emit("a_streaming_replay", device, total=res["total"],
         submitted=res["submitted"], admitted=res["admitted"],
         rejected=res["rejected"], chunks=res["chunks"],
         rollup_samples=res["rollup"]["samples"],
         wall_s=res["seconds"], rows_per_s=res["rows_per_s"],
         peak_rss_mb=res["peak_rss_mb"], warmup_s=warmup_s,
         compiles_in_window=compiles.n - n0)


def phase_columnar_admission(device, compiles, n: int):
    from benchmarks import bench_sched_throughput as bst

    pin_jax_backend()
    t0 = time.perf_counter()
    bst._run_arm("columnar", bst.BATCH)
    warmup_s = time.perf_counter() - t0

    pin_jax_backend()
    n0 = compiles.n
    dt, accepted, n = bst._run_arm("columnar", n)
    window_compiles = compiles.n - n0
    require(accepted == n, f"columnar admission accepted {accepted}/{n}")
    failures: list = []
    bst._check_backend_parity(failures)
    require(not failures, f"columnar admission: {failures}")
    emit("b_columnar_admission", device, n=n, batch=bst.BATCH,
         accepted=accepted, jax_numpy_parity=True, wall_s=dt,
         decisions_per_s=n / dt, warmup_s=warmup_s,
         compiles_in_window=window_compiles)


def phase_gateway(device):
    from examples.serve_fdn import build_serving_fdn, drive

    pin_jax_backend()
    t0 = time.perf_counter()
    cp, gw, all_fns = build_serving_fdn()
    results = drive(cp, gw, all_fns, duration_s=SERVE_DURATION_S)
    wall_s = time.perf_counter() - t0
    status: dict = {}
    for res in results:
        for inv in res.invocations:
            status[inv.status] = status.get(inv.status, 0) + 1
    requests = sum(status.values())
    answered = status.get("done", 0) + status.get("failed", 0)
    require(requests >= 100, f"only {requests} gateway requests were made")
    require(answered == requests,
            f"unanswered gateway requests: {status}")
    emit("c_gateway", device, platforms=len(cp.platforms),
         functions=len(all_fns), requests=requests,
         completed=status.get("done", 0),
         rejected=status.get("failed", 0), hedges=cp.hedge.hedges_sent,
         sim_s=cp.clock.now(), wall_s=wall_s)


def phase_golden_reports(device):
    from benchmarks.scenario_diff import diff_reports
    from repro.inspector import ScenarioReport, registry, run_scenario

    for name in GOLDEN_SCENARIOS:
        pin_jax_backend()
        t0 = time.perf_counter()
        payload = run_scenario(registry.get(name)).to_json()
        wall_s = time.perf_counter() - t0
        report = json.loads(payload)
        ScenarioReport.validate(report)
        golden_path = ROOT / "benchmarks" / "golden" / (
            name.replace("/", "_") + ".json")
        golden_text = golden_path.read_text()
        drifts = diff_reports(report, json.loads(golden_text))
        emit("d_golden_report", device, scenario=name,
             drifts=[str(d) for d in drifts],
             byte_identical=payload.strip() == golden_text.strip(),
             wall_s=wall_s)
        require(not drifts, f"{name}: {len(drifts)} metric(s) drift from "
                f"{golden_path.name}")


def _cascade_columns(f: int, p: int, seed: int):
    """Seeded estimator columns on a dyadic grid: every sum and product of
    the cascade is exact in float32 and float64, so the f32 device
    decision and the f64 NumPy oracle must agree bit for bit (ties
    included, broken first-lowest by both)."""
    import numpy as np
    rng = np.random.default_rng(seed)

    def grid(lo, hi, step, shape):
        return rng.integers(lo, hi, shape) * step

    return {
        "ewma_v": grid(1, 65, 0.125, (f, p)),
        "ewma_n": rng.integers(0, 6, (f, p)).astype(np.int32),
        "analytic_s": grid(1, 65, 0.125, (f, p)),
        "resp_h2": grid(1, 97, 0.125, (f, p)),
        "resp_n": rng.integers(0, 16, (f, p)).astype(np.int32),
        "data_s": grid(0, 33, 0.125, (f, p)),
        "nodes": grid(1, 9, 1.0, (p,)),
        "loaded_w": grid(4, 81, 0.25, (p,)),
        "alive": rng.random((f, p)) < 0.85,
        "cpu_util": grid(0, 9, 0.125, (p,)),
        "mem_util": grid(0, 9, 0.125, (p,)),
        "slo_s": grid(1, 49, 0.25, (f,)),
    }


def _numpy_cascade(cols, params):
    """The NumPy ``SLOCompositePolicy`` cascade on the same columns."""
    import numpy as np
    from repro.core.scheduler import SLOCompositePolicy
    from repro.kernels.policy_score import masked_argmin, predict_columns
    exec_s, p90_s, energy_j = predict_columns(
        np, cols["ewma_v"], cols["ewma_n"], cols["analytic_s"],
        cols["resp_h2"], cols["resp_n"], cols["nodes"], cols["loaded_w"])
    feats = {"alive": cols["alive"], "cpu_util": cols["cpu_util"],
             "mem_util": cols["mem_util"], "slo_s": cols["slo_s"],
             "exec_s": exec_s, "data_s": cols["data_s"], "p90_s": p90_s,
             "energy_j": energy_j}
    cost, kill = SLOCompositePolicy.cascade(feats, params)
    return masked_argmin(np, cost, kill == 0)


def phase_cascade(device):
    import numpy as np
    from repro.core.scheduler import SLOCompositePolicy
    from repro.kernels import policy_score as ps

    params = dict(SLOCompositePolicy.CASCADE_PARAMS, energy_weight=0.125)
    for seed, (f, p) in enumerate(CASCADE_SHAPES):
        cols = _cascade_columns(f, p, seed)
        unloaded = ((cols["cpu_util"] < params["cpu_threshold"])
                    & (cols["mem_util"] < params["mem_threshold"]))
        args = [cols[k] for k in ("ewma_v", "ewma_n", "analytic_s",
                                  "resp_h2", "resp_n", "data_s", "nodes",
                                  "loaded_w", "alive")]
        args += [unloaded, cols["slo_s"], params["energy_weight"]]
        t0 = time.perf_counter()
        ps.fused_composite_decide(*args)
        first_call_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        choice, ok = ps.fused_composite_decide(*args)
        call_s = time.perf_counter() - t0
        want = _numpy_cascade(cols, params)
        require(np.array_equal(ok, want[1]),
                f"ok differs from NumPy at {f}x{p}")
        require(np.array_equal(choice[ok], want[0][ok]),
                f"choices differ from NumPy at {f}x{p}")
        emit("e_cascade", device, shape=[f, p],
             feasible_rows=int(want[1].sum()), jit_eq_numpy=True,
             jit_first_call_s=first_call_s, jit_call_s=call_s)


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="run on any device; never reports ok")
    args = ap.parse_args(argv)
    try:
        from benchmarks.fdn_common import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's packages are not next to "
              f"this script ({e})", file=sys.stderr)
        return 2
    cache_dir = use_compile_cache()

    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: no TPU found: JAX's first device is "
              f"{dev.platform} ({dev.device_kind}); this smoke test runs "
              f"only on a TPU", file=sys.stderr)
        return 1
    emit("setup", device, compile_cache=cache_dir, jax=jax.__version__,
         rehearse=args.rehearse)
    compiles = CompileCounter()
    t0 = time.perf_counter()
    phase_streaming_replay(device, compiles)
    phase_columnar_admission(device, compiles,
                             4_000 if args.rehearse else 40_000)
    phase_gateway(device)
    phase_golden_reports(device)
    phase_cascade(device)
    emit("done", device, wall_s=time.perf_counter() - t0,
         compiles=compiles.n)
    if args.rehearse:
        print(json.dumps({"rehearsal": True, "device": device}))
        return 0
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
