"""Cost model — analytic compute/communication estimates for a captured
Program under a candidate sharding.

Parity: reference auto_parallel/cost_model.py and cost/ (op-level
CompOpCost/CommOpCost classes fed into the planner). TPU machine model:
MXU peak flops + HBM bandwidth per chip, ICI link bandwidth for
collectives (ring cost formulas; see the public scaling-book recipe the
design follows).
"""
from __future__ import annotations

import numpy as np

from jax.sharding import PartitionSpec as P

from ...core.tensor import Tensor
from .completion import Completer, _entries
from .partitioner import infer_reshard_comm, local_shape


# Published per-chip peaks, keyed by the string
# ``jax.devices()[0].device_kind`` reports. A device that is not here is
# an error for anything that divides by a peak (monitor/perf.py
# machine_spec), never a default.
DEVICE_PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s
    # HBM (and 1,600 Gbit/s of interconnect per chip, all links, both
    # ways). ici_bw is ONE link one way, 4.5e10 B/s — the public
    # scaling-book hardware table — which is what a ring collective on
    # one mesh axis sees.
    "TPU v5 lite": {"peak_flops": 197e12, "hbm_bw": 819e9,
                    "ici_bw": 45e9},
}


class MachineSpec:
    """Per-chip peak numbers of the machine a plan is made FOR. The
    planner runs offline (tools/llama7b_plan.py plans a v5e pod from a
    CPU host), so its default is a stated target — the v5e row of
    DEVICE_PEAKS — not a guess about the local device."""

    def __init__(self, peak_flops=None, hbm_bw=None, ici_bw=None):
        target = DEVICE_PEAKS["TPU v5 lite"]
        self.peak_flops = peak_flops or target["peak_flops"]
        self.hbm_bw = hbm_bw or target["hbm_bw"]
        self.ici_bw = ici_bw or target["ici_bw"]


def _numel(shape):
    return int(np.prod(shape)) if shape else 1


def op_flops(op_name, in_shapes, out_shapes):
    """Forward FLOPs (reference cost/comp_op_cost.py per-op formulas)."""
    if op_name in ("matmul", "mm", "linear"):
        if len(in_shapes) >= 2:
            x, w = in_shapes[0], in_shapes[1]
            m = _numel(x[:-1])
            k = x[-1] if x else 1
            n = w[-1] if w else 1
            return 2.0 * m * k * n
    if op_name == "bmm" and len(in_shapes) >= 2:
        x, w = in_shapes[0], in_shapes[1]
        return 2.0 * _numel(x) * w[-1]
    if op_name.startswith("conv"):
        # rough: 2 * out_numel * k_numel_per_out
        if len(in_shapes) >= 2 and out_shapes:
            w = in_shapes[1]
            return 2.0 * _numel(out_shapes[0]) * _numel(w[1:])
    # elementwise & the rest: one flop per output element
    return float(sum(_numel(s) for s in out_shapes))


def collective_cost_bytes(kind, nbytes, degree):
    """Ring-collective bytes on the wire per device (scaling-book ring
    formulas; reference cost/comm_op_cost.py roles)."""
    if degree <= 1 or kind == "identity" or kind == "slice":
        return 0.0
    if kind in ("all_reduce",):
        return 2.0 * nbytes * (degree - 1) / degree
    if kind in ("all_gather", "reduce_scatter"):
        return nbytes * (degree - 1) / degree
    if kind in ("all_to_all",):
        return nbytes * (degree - 1) / degree
    if kind == "collective_permute":
        return float(nbytes)
    return float(nbytes)


class CostEstimator:
    """estimate(program[, specs]) -> dict with flops/bytes/time
    (reference cost_model.py estimate_cost)."""

    def __init__(self, mesh=None, machine=None):
        from .. import mesh as _mesh

        self.mesh = mesh or _mesh.get_mesh()
        self.machine = machine or MachineSpec()

    def estimate(self, program, specs=None):
        specs = specs or Completer().complete_forward_annotation(program)
        total_flops = 0.0
        local_flops = 0.0
        comm_bytes = 0.0
        comms = []
        for rec in program.tape:
            tin = [l for l in rec.leaves if isinstance(l, Tensor)]
            in_shapes = [tuple(t.shape) for t in tin]
            out_shapes = [tuple(t.shape) for t in rec.outs]
            f = op_flops(rec.op_name, in_shapes, out_shapes)
            total_flops += f
            in_local = [local_shape(s, specs.get(id(t)), self.mesh)
                        for s, t in zip(in_shapes, tin)]
            out_local = [local_shape(s, specs.get(id(t)), self.mesh)
                         for s, t in zip(out_shapes, rec.outs)]
            local_flops += op_flops(rec.op_name, in_local, out_local)
            # contracted-dim sharding on matmul => psum of the output
            if rec.op_name in ("matmul", "mm", "linear", "bmm") \
                    and len(tin) >= 2:
                x = tin[0]
                xs = _entries(specs.get(id(x)) or P(), x.ndim)
                if xs and xs[-1] is not None:
                    axes = xs[-1] if isinstance(xs[-1], tuple) else (xs[-1],)
                    deg = int(np.prod([self.mesh.shape[a] for a in axes]))
                    nbytes = _numel(out_local[0]) * 4
                    b = collective_cost_bytes("all_reduce", nbytes, deg)
                    comm_bytes += b
                    comms.append((rec.op_name, "all_reduce", b))
        m = self.machine
        return {
            "total_flops": total_flops,
            "local_flops": local_flops,
            "comm_bytes": comm_bytes,
            "comms": comms,
            "compute_time": local_flops / m.peak_flops,
            "comm_time": comm_bytes / m.ici_bw,
            "time": local_flops / m.peak_flops + comm_bytes / m.ici_bw,
        }

    def reshard_cost(self, shape, src_spec, dst_spec):
        kind = infer_reshard_comm(src_spec, dst_spec, len(shape), self.mesh)
        deg = int(np.prod(list(self.mesh.shape.values())))
        nbytes = _numel(shape) * 4
        b = collective_cost_bytes(kind, nbytes, deg)
        return {"kind": kind, "bytes": b,
                "time": b / self.machine.ici_bw}
