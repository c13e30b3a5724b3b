#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is found by name from ``BENCHMARK.json``: its
configuration in ``configs/``, its traffic in ``traffic/``, the driver of
the traffic's ``kind`` in ``drivers/``, the plain reference in
``reference/``, the limits of ``correct`` in ``correct/<cell>.json`` and
one reader per per-layer metric in ``layer_metrics/``.
The last line of standard output is the result; the numbers compared
with the reference are the last lines of standard error.  Any backend but
a TPU of a kind ``peaks.json`` knows ends the run non-zero with no result.
"""
import time
T_PROCESS = time.perf_counter()

import argparse          # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import sys               # noqa: E402
import types             # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _log(msg):
    print("[bench] " + msg, file=sys.stderr, flush=True)


def _fail(msg, code=2):
    _log("FAILED: " + msg)
    return code


def find_cell(bench, name):
    for w in bench["workloads"]:
        if w["name"] == name:
            conf = next(c for c in bench["configs"]
                        if c["name"] == w["config"])
            return w, conf
    raise KeyError("no workload %r in BENCHMARK.json (has: %s)" % (
        name, ", ".join(w["name"] for w in bench["workloads"])))


def metrics_of(bench, group, cell):
    """The metrics of ``group`` that this cell reports."""
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


class Refused(Exception):
    """The run cannot start: unknown cell, wrong backend, unknown chip."""


def build_env(bench, workload, seed, seconds, trace, overrides=None):
    """Everything a driver needs for one run of one cell.  ``overrides``
    is for the benchmark's own tests and no command-line argument
    reaches it: ``platform`` (run on the CPU), ``config``, ``traffic`` and
    ``correct`` (keys laid over the files, for a tiny size),
    ``device_kind`` (a row of peaks), ``workloads`` (cells that
    ``BENCHMARK.json`` does not hold yet) and ``like`` (the cell whose
    limits and lists of metrics such a cell borrows)."""
    from benchmark import harness
    overrides = overrides or {}
    platform = overrides.get("platform", "tpu")
    bench = dict(bench, workloads=bench["workloads"]
                 + overrides.get("workloads", []))
    try:
        cell, conf = find_cell(bench, workload)
    except KeyError as e:
        raise Refused(str(e))
    if trace and platform != "tpu":
        raise Refused("--trace 1 asks for device metrics; only a TPU run "
                      "can give them")
    import jax
    devs = [d for d in jax.devices() if d.platform == platform]
    if jax.default_backend() != platform or len(devs) < cell["chips"]:
        raise Refused("cell %s needs %d %s chip(s); JAX found %s on backend "
                      "%s" % (cell["name"], cell["chips"], platform,
                              jax.devices(), jax.default_backend()))
    peaks = harness.load_json("peaks.json")["device_kinds"]
    kind = overrides.get("device_kind", devs[0].device_kind)
    if kind not in peaks:
        raise Refused("device kind %r is not in benchmark/peaks.json" % kind)

    from mxnet_tpu import config as mx_config
    cache_dir = mx_config.compile_cache_dir()
    like = overrides.get("like", cell["name"])
    with open(os.path.join(ROOT, conf["file"])) as f:
        cfg = json.load(f)
    cfg.update(overrides.get("config", {}))
    traffic = harness.load_json("traffic", cell["traffic"] + ".json")
    traffic.update(overrides.get("traffic", {}))
    env = types.SimpleNamespace(
        cell=cell["name"], cfg=cfg, traffic=traffic, seed=seed,
        seconds=seconds, trace=bool(trace), platform=platform,
        devices=devs[:cell["chips"]], t_process=T_PROCESS, log=_log,
        cfg_mod=harness.load_module("configs", conf["name"]),
        ref_mod=harness.load_module("reference", conf["name"]),
        like=like, correct=dict(
            harness.load_json("correct", like + ".json"),
            **overrides.get("correct", {})),
        spans=harness.Spans(), counters=harness.CompileCounters(),
        peaks=peaks[kind], calibrate=None)
    _log("cell %s seed %d seconds %g trace %d; cache %s; %d x %s"
         % (cell["name"], seed, seconds, trace, cache_dir,
            len(env.devices), kind))
    return env


def main(argv=None, overrides=None):
    """Run one cell; returns the exit code."""
    from benchmark import harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    try:
        env = build_env(bench, a.workload, a.seed, a.seconds, a.trace,
                        overrides)
    except Refused as e:
        return _fail(str(e))
    obs = harness.load_module("drivers", env.traffic["kind"]).run(env)
    obs["peaks"] = env.peaks
    obs["spans"] = env.spans
    obs["e2e"]["setup_s"] = obs["setup_s"]
    if obs["compiles_in_window"]:
        return _fail("%d compile request(s) inside the measured window"
                     % obs["compiles_in_window"], code=3)

    metrics = {}
    if a.trace:
        for m in metrics_of(bench, "per_layer", env.like):
            value = harness.load_module("layer_metrics", m["name"]).read(obs)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in metrics_of(bench, "end_to_end", env.like):
            metrics[m["name"]] = {"value": obs["e2e"][m["name"]],
                                  "unit": m["unit"]}
    device = {"platform": env.devices[0].platform,
              "kind": env.devices[0].device_kind, "count": len(env.devices),
              "memory_peak_bytes": obs["memory_peak_bytes"]}
    checks = obs["checks"]
    result = {"correct": checks.correct, "attempted": obs["attempted"],
              "failed": obs["failed"], "metrics": metrics, "device": device}
    if a.trace:
        trace = obs["trace"]
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    result["end_to_end_seen"] = obs["e2e"]
    result["counts"] = obs["counts"]
    result["checks"] = checks.as_dict()
    print(json.dumps(result), flush=True)
    for line in checks.lines():
        print(line, file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
