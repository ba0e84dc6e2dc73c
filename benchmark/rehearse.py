#!/usr/bin/env python3
"""Compile each cell's step programs for a described TPU, without a chip.

    python3 benchmark/rehearse.py [--workload <cell>] [--topology v5e:1x1]

For every cell of ``BENCHMARK.json`` (or the one named) this builds the
model on the host at the cell's real sizes with zero weights, hands the
runner's ``rehearse`` the described device, and prints for each program
the compiler's ``memory_analysis()`` and the Mosaic kernels present. It
runs nothing: what it prints is buffer assignment, not a device reading,
and no time. It is how a later PR sizes a new cell before it spends chip
time: a program that does not fit, a kernel the TPU compiler refuses and
a reference path taken in a kernel's place all show here.

The program picks its kernels by ``jax.default_backend()``; this script
makes that say "tpu" while it lowers, and makes every initializer draw
zeros, in this process only.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
GB = 1e9


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", action="append")
    parser.add_argument("--topology", default="v5e:1x1")
    args = parser.parse_args(argv)
    sys.path.insert(0, HERE)
    sys.path.insert(0, ROOT)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import run as bench
    import trace_reduce
    from paddle_tpu.core import dtype as _dtype
    from paddle_tpu.core.tensor import Parameter
    from paddle_tpu.nn import initializer

    served = {}     # the cell's dtype: zeros are made in it at once

    def zeros(self, shape, dtype=None, name=None):
        dt = _dtype.to_jax(served.get("dtype") or dtype
                           or _dtype.get_default_dtype())
        return Parameter(jnp.zeros(tuple(int(s) for s in shape), dt),
                         name=name)

    initializer.Initializer.create = zeros
    jax.config.update("jax_enable_compilation_cache", False)
    bounds = tuple(int(b) for b in args.topology.split(":")[1].split("x"))
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name=args.topology,
        chips_per_host_bounds=bounds + (1,) * (3 - len(bounds)))
    manifest = bench.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    names = args.workload or [w["name"] for w in manifest["workloads"]]
    for name in names:
        cell, config, traffic, family, runner = bench.resolve(manifest, name)
        served["dtype"] = config.get("torch_dtype")
        devices = list(topo.devices)[:cell["chips"]]
        sharding = SingleDeviceSharding(devices[0])

        def placed(a):
            return jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a),
                                        sharding=sharding)

        print("== %s: config %s, traffic %s, %s x %d (%s)"
              % (name, cell["config"], cell["traffic"],
                 devices[0].device_kind, len(devices), args.topology),
              flush=True)
        t0 = time.time()
        real_backend = jax.default_backend
        jax.default_backend = lambda: "tpu"
        try:
            programs = runner.rehearse(family, config, traffic, devices,
                                       placed)
        finally:
            jax.default_backend = real_backend
        for what, compiled in programs:
            mem = compiled.memory_analysis()
            total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                     - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
            print("   %-14s arguments %.3f GB, outputs %.3f GB (aliased "
                  "%.3f GB), temporaries %.3f GB, code %.3f GB: %.3f GB in "
                  "all; Mosaic kernels %s"
                  % (what, mem.argument_size_in_bytes / GB,
                     mem.output_size_in_bytes / GB,
                     mem.alias_size_in_bytes / GB,
                     mem.temp_size_in_bytes / GB,
                     mem.generated_code_size_in_bytes / GB, total / GB,
                     json.dumps(trace_reduce.kernel_counts(
                         compiled.as_text()), sort_keys=True)),
                  flush=True)
        print("   (%.0f s)" % (time.time() - t0), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
