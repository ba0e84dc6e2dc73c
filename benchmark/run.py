#!/usr/bin/env python3
"""Run one cell of the benchmark once, in this process, on the chip.

    python3 benchmark/run.py --workload <cell> --seed <n>
                             --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``. Everything
that belongs to it is a file found by the name written there:

    configs/<config>.json           the model's sizes; names its family
    families/<family>.py            model builder, plain reference, arithmetic
    traffic/<traffic>.json          the mix's parameters; names its runner
    runners/<runner>.py             one kind of traffic
    layer_metrics/<metric>.py       one per-layer metric's reader

This file holds no name of a cell, configuration, family, mix, runner or
metric. With ``--trace 0`` the last line of standard output carries the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics and a
breakdown of the traced part of the window. Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse                                     # noqa: E402
import faulthandler                                 # noqa: E402
import importlib.util                               # noqa: E402
import json                                         # noqa: E402
import os                                           # noqa: E402
import shutil                                       # noqa: E402
import sys                                          # noqa: E402
import types                                        # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# a run that outlives the driver's limit says where it hangs
DEADLINE_S = 1150


def log(msg):
    print("[bench] " + msg, flush=True)


def load_module(directory, name, here=HERE):
    """The module ``<here>/<directory>/<name>.py``. Names hold dots and
    dashes, so this is by path, not by import."""
    path = os.path.join(here, directory, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError("%s names %r but there is no %s"
                                % (directory, name, path))
    spec = importlib.util.spec_from_file_location(
        "bench_%s_%s" % (directory, name.replace(".", "_").replace(
            "-", "_")), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_json(path):
    with open(path) as f:
        return json.load(f)


def find(entries, name, what):
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise KeyError("BENCHMARK.json has no %s named %r (it has %s)"
                   % (what, name, [e["name"] for e in entries]))


def applies(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(manifest, cell_name, root=ROOT, here=HERE):
    """The cell's entry, configuration, mix, family and runner modules."""
    cell = find(manifest["workloads"], cell_name, "workload")
    config_entry = find(manifest["configs"], cell["config"], "config")
    config = load_json(os.path.join(root, config_entry["file"]))
    traffic = load_json(os.path.join(here, "traffic",
                                     cell["traffic"] + ".json"))
    family = load_module("families", config["family"], here)
    runner = load_module("runners", traffic["runner"], here)
    return cell, config, traffic, family, runner


def read_layer_metrics(manifest, cell_name, obs, here=HERE):
    """{name: value} of the cell's per-layer metrics whose reader found
    something to read."""
    out = {}
    for metric in manifest["per_layer"]:
        if not applies(metric, cell_name):
            continue
        value = load_module("layer_metrics", metric["name"], here).read(obs)
        if value is None:
            log("layer metric %s: nothing to read" % metric["name"])
        else:
            out[metric["name"]] = float(value)
    return out


def result_line(manifest, cell_name, trace, result, setup_s, layer_values,
                device, reduced):
    """The contract's last line, as a dict."""
    section = manifest["per_layer"] if trace else manifest["end_to_end"]
    if trace:
        values = layer_values
    else:
        values = dict(result["end_to_end"], setup_s=setup_s)
    metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
               for m in section
               if applies(m, cell_name) and m["name"] in values}
    line = {
        "correct": all(ok for ok, _ in result["checks"].values()),
        "attempted": int(result["attempted"]),
        "failed": int(result["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if trace and reduced is not None:
        line["device"] = dict(device, busy_s=reduced["busy_s"],
                              window_s=reduced["window_s"])
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    return line


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    sys.path.insert(0, HERE)
    sys.path.insert(0, ROOT)
    cell, config, traffic, family, runner = resolve(manifest, args.workload)

    import jax

    import peaks
    import trace_reduce
    from paddle_tpu.core import compile_cache

    cache_dir = compile_cache.configure()
    # cache every program, not only those that took a second to compile:
    # a program near JAX's threshold is cached by some runs and not by
    # others, and then set-up flaps
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if platform != "tpu" or len(devices) < cell["chips"]:
        print("[bench] this benchmark runs on the chip only: JAX reports "
              "platform %r with %d device(s), the cell asks for %d TPU "
              "chip(s)" % (platform, len(devices), cell["chips"]),
              file=sys.stderr, flush=True)
        return 2
    devices = devices[:cell["chips"]]
    log("cell %s: config %s (family %s), traffic %s (runner %s), seed %d, "
        "%s %s x %d, compile cache %s"
        % (cell["name"], cell["config"], config["family"], cell["traffic"],
           traffic["runner"], args.seed, platform, kind, len(devices),
           cache_dir))

    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(HERE, ".trace", cell["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
    # what a runner gets: the cell's files, the run's arguments, the
    # devices
    result = runner.run(types.SimpleNamespace(
        config=config, traffic=traffic, family=family, seed=args.seed,
        seconds=args.seconds, devices=devices, trace_dir=trace_dir,
        log=log))
    setup_s = result["window_open_t"] - T_START
    log("set-up %.3f s; window %.3f s" % (setup_s, result["window_s"]))

    reduced = None
    layer_values = {}
    if args.trace:
        if result["trace"] is not None:
            path = trace_reduce.find_xplane(result["trace"]["dir"])
            loaded = trace_reduce.load(path)
            reduced = trace_reduce.reduce(
                loaded, result["trace"]["window_spans"],
                result["trace"]["kernels"])
            log("trace: %s, %d bytes; device planes %s; %d host spans"
                % (os.path.relpath(path, ROOT), os.path.getsize(path),
                   sorted(loaded["devices"]), len(loaded["host"])))
        if reduced is None:
            log("check trace: FAILED (no device operation inside the "
                "benchmark's spans)")
            return 1
        obs = dict(result["observations"], trace=reduced, config=config,
                   traffic=traffic, family=family, chips=len(devices),
                   peaks=peaks.peaks_for(kind), log=log,
                   end_to_end=result["end_to_end"])
        layer_values = read_layer_metrics(manifest, cell["name"], obs)
        for name, seconds in reduced["device_ops"]:
            log("device op %-40s %.6f s (%.1f %% of busy)"
                % (name, seconds, 100 * seconds / reduced["busy_s"]))
        for name, seconds in reduced["idle_gaps"]:
            log("idle under %-37s %.6f s" % (name, seconds))
        shutil.rmtree(trace_dir, ignore_errors=True)

    for name, (ok, detail) in sorted(result["checks"].items()):
        log("check %s: %s (%s)" % (name, "ok" if ok else "FAILED", detail))
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    print(json.dumps(result_line(
        manifest, cell["name"], bool(args.trace), result, setup_s,
        layer_values, device, reduced)), flush=True)
    return 0


if __name__ == "__main__":
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)
    sys.exit(main())
