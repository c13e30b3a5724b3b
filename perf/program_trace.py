"""One cell of the benchmark with ``--trace 1``, and the device's time by
compiled program beside its result (``benchmark/trace_programs.py``).

    python perf/program_trace.py --workload <cell> --seed <n>
    python perf/program_trace.py --workload <cell> --seed <n> \\
        --seconds 20 --trace-seconds 0.3 --keep <dir>

Runs ``benchmark/run.py`` in this process.  Before the benchmark reduces
the trace and removes it, the trace is read again with
``trace_programs.reduce`` over its ``bench:window`` span, and after the
result line one more line is printed: ``{"programs": {name: {"runs",
"device_s", "median_ms"}}, "named_busy_share", "idle_gaps_program",
"idle_gaps", "busy_s", "window_s", "bytes", "stalls"}``.  ``--keep DIR``
copies the trace's ``.xplane.pb`` to ``DIR/<cell>.xplane.pb``;
``--trace-seconds`` sets the traced part of the window (4 s in the
benchmark); ``--trace 0`` runs the cell untraced; ``--overrides JSON``
hands ``run.main`` the keys its tests lay over a cell (``config``,
``traffic``, ``correct``: a tiny size on the chip).  ``stalls`` lists, from
the program's ring, the collections of Python's heap of 1 ms or more
(``py.gc``) and the five longest ``decode.step`` or ``fit.step``
iterations, each as ``[name, seconds into the window, ms, generation]``.
Exits with the benchmark's code.
"""
import argparse
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--trace-seconds", type=float, default=None)
    ap.add_argument("--keep", default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--overrides", type=json.loads, default=None)
    a = ap.parse_args(argv)

    from benchmark import harness, run, trace_programs
    if a.trace_seconds is not None:
        harness.TRACE_SECONDS = a.trace_seconds
    seen = {}
    reduce = harness.Tracer.reduce

    def reduce_and_read(tracer):
        path = trace_programs.newest_file(tracer.dir)
        r = trace_programs.reduce_window(trace_programs.load_file(path))
        seen.update({k: r[k] for k in (
            "window_s", "busy_s", "programs", "named_busy_share",
            "idle_gaps", "idle_gaps_program")}, bytes=os.path.getsize(path))
        if a.keep:
            os.makedirs(a.keep, exist_ok=True)
            shutil.copy(path, os.path.join(a.keep,
                                           a.workload + ".xplane.pb"))
        return reduce(tracer)
    harness.Tracer.reduce = reduce_and_read
    window = []
    load = harness.load_module

    def load_and_keep_window(*parts):
        mod = load(*parts)
        if parts[0] == "drivers":
            drive = mod.run

            def run_and_keep(env):
                obs = drive(env)
                window.append(obs["window"])
                return obs
            mod.run = run_and_keep
        return mod
    harness.load_module = load_and_keep_window
    code = run.main(["--workload", a.workload, "--seed", str(a.seed),
                     "--seconds", str(a.seconds), "--trace", str(a.trace)],
                    overrides=a.overrides)
    if window:
        seen["stalls"] = stalls(*window[0])
    print(json.dumps(dict(seen, cell=a.workload)), flush=True)
    return code


def stalls(lo, hi):
    """Long collections and the longest iterations of the window, from
    the program's ring (``perf_counter`` stamps, the window's clock)."""
    from mxnet_tpu.telemetry import timeline
    tl = timeline.peek()
    evs = [e for e in (tl.events() if tl is not None else [])
           if lo <= e["mono"] <= hi and e.get("dur") is not None]
    gcs = [["py.gc", e["mono"] - lo, 1e3 * e["dur"],
            (e.get("args") or {}).get("generation")]
           for e in evs if e["name"] == "py.gc"]
    steps = sorted((e for e in evs
                    if e["name"] in ("decode.step", "fit.step")),
                   key=lambda e: -e["dur"])[:5]
    return gcs + [[e["name"], e["mono"] - lo, 1e3 * e["dur"], None]
                  for e in steps]


if __name__ == "__main__":
    sys.exit(main())
