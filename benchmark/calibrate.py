#!/usr/bin/env python3
"""Read what the limits of ``correct`` are set from, on the chip, at a
cell's own size: for each seed the numbers a sound run compares (the
lower reading), the same numbers with the control in the program's place
(the reference in the precision the cell's ``correct/<cell>.json`` names
as ``control``, the step below the one its configuration states) and,
for a training cell, with each fault planted in the reference put in the
program's place.

    python3 benchmark/calibrate.py --workload <cell> --seeds 11,12,13 \\
        --seconds 2 [--control 3] [--out chiprun_out/calibrate.jsonl]

One process reads every seed; the control and the faults are read on the
first ``--control`` seeds.  One JSON line per seed.  The benchmark's own
runs never call this; PERF.md section 2 holds what it read.
"""
import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _quantiles(gaps):
    import numpy as np
    g = np.asarray(gaps)
    return {"max": float(g.max()), "p99": float(np.percentile(g, 99)),
            "p50": float(np.percentile(g, 50)), "n": int(g.size),
            "nonzero": int((g > 0).sum())}


def main(argv=None, overrides=None):
    from benchmark import harness, run
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--out", default=None)
    a = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    seeds = [int(s) for s in a.seeds.split(",")]
    out = open(a.out, "a") if a.out else None
    for n, seed in enumerate(seeds):
        env = run.build_env(bench, a.workload, seed, a.seconds, 0, overrides)
        env.t_process = time.perf_counter()
        rec = {"cell": env.cell, "seed": seed}
        with_control = n < a.control
        control = env.correct["control"]

        def hook(ref, rerun, program=None, compare=None):
            if program is None:                 # a served model
                rec["program"] = _quantiles(ref["gaps"])
                if with_control:
                    rec["control_" + control] = _quantiles(
                        rerun(precision=control)["gaps"])
                return

            def read(name, got):
                # no limits: every number is logged, none compared
                rec[name] = compare(got, ref, {}).logged

            read("program", program)
            if not with_control:
                return
            read("control_" + control, rerun(precision=control))
            faults = {"half_batch": 0.5}
            if len(env.devices) > 1:
                faults["no_exchange"] = 1.0 / len(env.devices)
            for name, keep in faults.items():
                read("fault_" + name, rerun(keep=keep))

        env.calibrate = hook
        obs = harness.load_module("drivers", env.traffic["kind"]).run(env)
        rec["correct"] = obs["checks"].correct
        rec["e2e"] = obs["e2e"]
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
        del obs, env, hook
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
