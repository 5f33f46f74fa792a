"""Run one cell traced with the program's own host spans on, and reduce them.

    python3 fdnbench/program_trace.py --workload <cell> --seed <n> \\
        --seconds <s> [--trace-out DIR]

Runs the cell as ``run.py --trace 1`` does, and besides attaches the
control plane's tracer (``FDNControlPlane.attach_tracer()``) for the
traced window, so the trace also holds the program's ``fdn/`` spans and
counters (``repro.obs.hostspans``).  The last line of standard output is
one JSON object: ``result``, as ``run.py --trace 1`` prints it (the
harness-span metrics, computed as there), ``program``, the reduction of
the ``fdn/`` spans (``programspans.summarize``: ``program_ms``,
``program_counts``, ``metrics``, ``idle_gaps_program``,
``longest_spans``), and ``window``, the window line (rows, elapsed
seconds, late times), to set against a run without the tracer.
``--trace-out DIR`` keeps the trace as ``DIR/<cell>.xplane.pb``.  Exits 1
without a result when JAX finds no TPU, as ``run.py`` does.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _with_tracer(install_spans):
    """``layers.install_spans`` that also attaches the program's tracer;
    the harness installs the spans just before the trace starts."""
    def install(wraps, dep):
        install_spans(wraps, dep)
        dep.cp.attach_tracer()
    return install


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace-out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from fdnbench import harness, layers, programspans, tracereduce
    harness.use_compile_cache()
    out_dir = args.trace_out or tempfile.mkdtemp(prefix="fdnbench-prog-")
    install_spans = layers.install_spans
    layers.install_spans = _with_tracer(install_spans)
    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               True, t_start=T_START, trace_out=out_dir)
    except harness.NoChip as e:
        print(f"fdnbench: {e}", file=sys.stderr)
        return 1
    finally:
        layers.install_spans = install_spans
    path = os.path.join(out_dir, f"{args.workload}.xplane.pb")
    t0 = time.perf_counter()
    program = programspans.summarize(tracereduce.load_xplane(path),
                                     programspans.load(path),
                                     out.window["batches"])
    program["load_s"] = time.perf_counter() - t0
    program["trace_bytes"] = os.path.getsize(path)
    if args.trace_out is None:
        shutil.rmtree(out_dir, ignore_errors=True)
    window = {k: v for k, v in out.window.items()
              if not hasattr(v, "shape")}
    print(json.dumps({"result": out.result, "program": program,
                      "window": window}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
