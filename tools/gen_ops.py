#!/usr/bin/env python
"""Op-surface codegen.

Parity: the reference generates its C++ API, python C bindings and grad
nodes from a single YAML op inventory at build time
(/root/reference/paddle/phi/api/yaml/generator/api_gen.py,
paddle/fluid/eager/auto_code_generator/generator/eager_gen.py,
python_c_gen.py; re-exported flat as python/paddle/_C_ops.py:19). Here the
kernel bodies are pure-JAX primitives registered at import, so codegen runs
the other direction with the same artifacts:

  1. introspect the live registry -> paddle_tpu/ops/ops.yaml
     (name, signature, differentiable, defining module) — the single
     reviewable op inventory;
  2. emit paddle_tpu/_C_ops.py — the flat eager-op namespace the
     reference exposes as `paddle._C_ops` (generated file, checked in).

Run:  python tools/gen_ops.py   (rewrites both artifacts)
"""
from __future__ import annotations

import inspect
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HEADER = (
    "# GENERATED by tools/gen_ops.py — do not edit by hand.\n"
    "#\n"
    "# Direction note (deliberate inversion of the reference): the\n"
    "# reference's ops.yaml is the SOURCE from which C++/bindings are\n"
    "# generated; here op bodies are pure-JAX primitives registered at\n"
    "# import, and this file is the generated, reviewable INVENTORY of\n"
    "# them (plus the _C_ops.py binding surface). What the reference's\n"
    "# direction buys — catching a missing op — is covered instead by\n"
    "# the op-coverage gate (tools/op_coverage.py + tests/\n"
    "# test_op_coverage.py), which diffs this inventory against the\n"
    "# reference's full 614 PHI kernel-name list on every run, and by\n"
    "# tests/test_native.py asserting registry<->yaml sync.\n"
)


def collect():
    import paddle_tpu  # noqa: F401  (registers every op)
    # model/parallel modules register their ops lazily on import
    import paddle_tpu.models.gpt  # noqa: F401
    import paddle_tpu.models.llama  # noqa: F401
    import paddle_tpu.parallel  # noqa: F401
    from paddle_tpu.core.dispatch import WRAPPERS

    ops = []
    for name in sorted(WRAPPERS):
        w = WRAPPERS[name]
        try:
            sig = str(inspect.signature(w.raw_fn))
        except (TypeError, ValueError):
            sig = "(...)"
        ops.append({
            "op": name,
            "signature": sig,
            "differentiable": not w.nondiff,
            "module": w.raw_fn.__module__,
        })
    return ops


def write_yaml(ops):
    path = os.path.join(REPO, "paddle_tpu", "ops", "ops.yaml")
    with open(path, "w") as f:
        f.write(HEADER)
        f.write("# Op inventory (reference paddle/phi/api/yaml/ops.yaml "
                "analog): %d ops.\n" % len(ops))
        for o in ops:
            f.write("- op : %s\n" % o["op"])
            f.write("  args : \"%s\"\n" % o["signature"])
            f.write("  differentiable : %s\n"
                    % ("true" if o["differentiable"] else "false"))
            f.write("  module : %s\n" % o["module"])
    return path


def write_c_ops(ops):
    path = os.path.join(REPO, "paddle_tpu", "_C_ops.py")
    with open(path, "w") as f:
        f.write(HEADER)
        f.write('"""Flat eager-op namespace (reference '
                "python/paddle/_C_ops.py:19 re-exporting core.eager.ops; "
                "here each symbol is the registered dispatch wrapper "
                'from paddle_tpu.core.dispatch.WRAPPERS)."""\n')
        # Lazy resolution: importing _C_ops must not drag in the model zoo.
        # Each op resolves on first attribute access by importing only its
        # defining module (which registers it) and caching the wrapper.
        f.write("_OP_MODULES = {\n")
        for o in ops:
            f.write('    "%s": "%s",\n' % (o["op"], o["module"]))
        f.write("}\n\n")
        f.write(
            "def __getattr__(name):\n"
            "    mod = _OP_MODULES.get(name)\n"
            "    if mod is None:\n"
            "        raise AttributeError(\n"
            '            "module paddle_tpu._C_ops has no op %r" % name)\n'
            "    import importlib\n"
            "    importlib.import_module(mod)\n"
            "    from .core.dispatch import WRAPPERS\n"
            "    fn = WRAPPERS[name]\n"
            "    globals()[name] = fn\n"
            "    return fn\n\n")
        f.write("__all__ = list(_OP_MODULES)\n")
    return path


def main():
    ops = collect()
    y = write_yaml(ops)
    c = write_c_ops(ops)
    print("wrote %s (%d ops)\nwrote %s" % (y, len(ops), c))


if __name__ == "__main__":
    main()
