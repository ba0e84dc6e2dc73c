"""Op-coverage audit: reference PHI kernel names vs this framework's op
registry (VERDICT r1 item 8).

Extracts every PD_REGISTER_KERNEL name from the reference's
paddle/phi/kernels/ tree, normalizes the naming differences (grad
suffixes, sparse/fused/legacy families, backend duplicates), and diffs
against paddle_tpu's OPS registry + public functional/tensor namespaces.
Writes OP_COVERAGE.md at the repo root.

Run:  python tools/op_coverage.py [--reference /root/reference]
"""
from __future__ import annotations

import argparse
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# reference kernels that are artifacts of the CUDA/fluid architecture,
# not user capabilities — a TPU-native framework has no analog to build.
# (kept visible in the report under "n/a by design" with the reason)
NA_BY_DESIGN = {
    # memory/layout/device plumbing (XLA/PJRT owns these)
    "memcpy": "XLA buffer assignment owns transfers",
    "memcpy_d2h": "PJRT device_get",
    "memcpy_h2d": "PJRT device_put",
    "memcpy_d2h_multi_io": "PJRT",
    "transfer_layout": "XLA layout assignment",
    "data_transform": "jit boundary handles dtype/layout",
    # fluid legacy / infrastructure ops
    "assign_pos": "MoE rows are sorted by expert (parallel/moe.py)",
    "number_count": "group sizes of the sorted rows (parallel/moe.py)",
    "limit_by_capacity": "no capacity: parallel/moe.py is dropless",
    "prune_gate_by_capacity": "no capacity: parallel/moe.py is dropless",
    "random_routing": "parallel/moe.py gates",
    "seed": "framework.random key system",
    "ftrl": "CPU PS-era optimizer; not in paddle.optimizer public API",
    "dpsgd": "differential-privacy contrib op outside core API",
    "nop": "scheduling artifact",
    "run_program": "jit.to_static executes captured programs directly",
    "fetch_v2": "Executor returns fetch values natively",
    "feed_with_place": "Executor feed",
    "print": "Python",
    "share_buffer": "functional arrays",
    "share_data": "functional arrays",
    "shadow_output": "interpreter artifact",
    "shadow_feed": "interpreter artifact",
    "select_input": "lax.cond lowering",
    "select_output": "lax.cond lowering",
    "tensor_array_to_tensor": "no LoD TensorArray; jnp stacking",
    "reorder_lod_tensor_by_rank": "no LoD",
    "lod_reset": "no LoD",
    "is_empty": "static shapes",
    "read_file": "io pipeline is host-side (paddle_tpu.io)",
    "save": "framework.io",
    "load": "framework.io",
    "save_combine": "framework.io",
    "load_combine": "framework.io",
    "uniform_random_batch_size_like": "static shapes make _like rng trivial",
    "c_comm_init_all": "XLA collectives need no comm init",
    "c_gen_nccl_id": "no NCCL",
    "c_wait_comm": "XLA schedules collectives",
    "c_wait_compute": "XLA schedules collectives",
    "sparse_momentum": "SelectedRows-free design (dense momentum)",
    "get_tensor_from_selected_rows": "no SelectedRows",
    "merge_selected_rows": "no SelectedRows",
    "clip_by_norm_sr": "no SelectedRows",
    "fused_adam": "optimizer update is one fused XLA module already",
    "fused_linear_param_grad_add": "XLA fuses",
    "fused_embedding_eltwise_layernorm": "XLA fuses",
    "fused_fc_elementwise_layernorm": "XLA fuses",
    "fusion_group": "XLA fusion",
    "fusion_gru": "XLA fuses the lax.scan GRU",
    "fusion_lstm": "XLA fuses the lax.scan LSTM",
    "fusion_repeated_fc_relu": "XLA fuses",
    "fusion_seqconv_eltadd_relu": "no LoD sequence ops",
    "fusion_seqexpand_concat_fc": "no LoD sequence ops",
    "fusion_seqpool_concat": "no LoD sequence ops",
    "fusion_seqpool_cvm_concat": "no LoD sequence ops",
    "fusion_squared_mat_sub": "XLA fuses",
    "fusion_transpose_flatten_concat": "XLA fuses",
    "fused_elemwise_add_activation": "XLA fuses",
    "fused_scale_bias_relu_conv_bn": "XLA fuses",
    "fused_scale_bias_add_relu": "XLA fuses",
    "fused_dconv_drelu_dbn": "XLA fuses",
    "fused_dot_product_attention": "kernels/flash_attention.py",
    "fused_conv2d_add_act": "XLA fuses",
    "conv2d_fusion_cutlass": "vendor kernel",
    "fc": "nn.Linear + XLA fusion",
    "squeeze_excitation_block": "composite of existing ops",
    "yolo_box_head": "detection-serving fusion outside API surface",
    "yolo_box_post": "detection-serving fusion outside API surface",
    "fused_multi_transformer_int8": "quantization path differs (pass-based)",
    "fused_multi_transformer_cachekv_layout_trans": "serving artifact",
    "self_dp_attention": "CPU-only oneDNN fusion",
    "skip_layernorm": "XLA fuses",
    "fused_token_prune": "TRT-era serving op",
    "fused_gate_attention": "flash attention covers",
    "resnet_basic_block": "XLA fuses whole blocks",
    "resnet_unit": "XLA fuses whole blocks",
    "cudnn_lstm": "lax.scan LSTM",
    "miopen_lstm": "lax.scan LSTM",
    "max_pool2d_v2": "pool2d covers",
    "legacy_bilinear_interp": "bilinear_interp covers",
    "legacy_nearest_interp": "nearest_interp covers",
    "legacy_expand": "expand covers",
    "legacy_expand_grad": "expand covers",
    "legacy_reshape": "reshape covers",
    "legacy_slice": "slice covers",
    "legacy_generate_proposals": "generate_proposals covers",
    "quantize_linear_deprecated": "quantize_linear covers",
    "dequantize_linear_deprecated": "dequantize_linear covers",
    "moving_average_abs_max_scale": "quantization observers (python)",
    "straight_through_estimator": "quantization STE (python)",
    "straight_through_estimator_grad": "quantization STE (python)",
    "check_memory_continue": "XLA buffer assignment (no fused-buffer check)",
    "coalesce_tensor": "XLA fuses grad buffers; no flat-buffer op needed",
    "conv2d_fusion": "XLA fuses conv+bias+act",
    "convdnn": "backend-specific conv dispatch; XLA lowers conv directly",
    "fused_conv2d": "XLA fuses",
    "fused_softmax_mask": "XLA fuses mask+softmax",
    "merged_adam": "multi-tensor apply; the whole update is one XLA module",
    "merged_momentum": "multi-tensor apply; one XLA module",
    "npu_identity": "vendor (Ascend) artifact",
    "mask": "sparse masking via dense where() under GSPMD",
    "mask_helper": "sparse masking via dense where()",
    "sparse_mask": "sparse masking via dense where()",
    "sparse_mask_helper": "sparse masking via dense where()",
}

# reference-name (or stripped base) -> (display, target) where target is a
# MACHINE-RESOLVABLE dotted path under the paddle_tpu package ("Tensor.x"
# addresses a Tensor method/operator). tests/test_op_coverage.py resolves
# every target at gate time, so an alias cannot silently rot.
REF_TO_OURS = {
    "add": ("elementwise add (+)", "Tensor.__add__"),
    "grad_add": ("add", "add"),
    "add_n": ("add_n", "add_n"),
    "subtract": ("- operator", "Tensor.__sub__"),
    "multiply": ("* operator", "Tensor.__mul__"),
    "divide": ("/ operator", "Tensor.__truediv__"),
    "matmul_with_flatten": ("matmul", "matmul"),
    "batch_norm": ("F.batch_norm", "nn.functional.batch_norm"),
    "sync_batch_norm": ("nn.SyncBatchNorm", "nn.SyncBatchNorm"),
    "fused_bn_add_activation":
        ("F.batch_norm + XLA fusion", "nn.functional.batch_norm"),
    "cross_entropy_with_softmax": ("softmax_with_cross_entropy",
                                   "nn.functional.softmax_with_cross_entropy"),
    "c_softmax_with_cross_entropy":
        ("parallel_softmax_cross_entropy",
         "parallel.mp_layers.parallel_softmax_cross_entropy"),
    "sum": ("sum", "sum"),
    "mean": ("mean", "mean"),
    "mean_all": ("mean", "mean"),
    "flash_attn": ("kernels.flash_attention",
                   "kernels.flash_attention.flash_attention"),
    "flash_attn_unpadded": ("kernels.flash_attention (segment_ids)",
                            "kernels.flash_attention.flash_attention"),
    "fused_attention": ("kernels.flash_attention",
                        "kernels.flash_attention.flash_attention"),
    "memory_efficient_attention": ("kernels.flash_attention",
                                   "kernels.flash_attention.flash_attention"),
    "variable_length_memory_efficient_attention":
        ("F.variable_length_attention",
         "nn.functional.variable_length_attention"),
    "fused_multi_head_attention":
        ("F.scaled_dot_product_attention",
         "nn.functional.scaled_dot_product_attention"),
    "dropout_nd": ("F.dropout", "nn.functional.dropout"),
    "fused_dropout_add": ("F.dropout + XLA fusion", "nn.functional.dropout"),
    "c_allreduce": ("distributed.all_reduce", "distributed.all_reduce"),
    "mp_allreduce_sum": ("distributed.all_reduce", "distributed.all_reduce"),
    "all_reduce": ("distributed.all_reduce", "distributed.all_reduce"),
    "reduce": ("distributed.reduce", "distributed.reduce"),
    "c_allgather": ("distributed.all_gather", "distributed.all_gather"),
    "all_gather": ("distributed.all_gather", "distributed.all_gather"),
    "c_reducescatter": ("distributed.reduce_scatter",
                        "distributed.reduce_scatter"),
    "c_broadcast": ("distributed.broadcast", "distributed.broadcast"),
    "broadcast_tensors": ("broadcast_tensors", "broadcast_tensors"),
    "all_to_all": ("distributed.alltoall", "distributed.alltoall"),
    "global_scatter": ("distributed.utils.global_scatter (moe)",
                       "distributed.utils.global_scatter"),
    "global_gather": ("distributed.utils.global_gather (moe)",
                      "distributed.utils.global_gather"),
    "send_v2": ("distributed.send", "distributed.send"),
    "p_send": ("distributed.send", "distributed.send"),
    "partial_send": ("partial_send", "distributed.collective.partial_send"),
    "recv_v2": ("distributed.recv", "distributed.recv"),
    "p_recv": ("distributed.recv", "distributed.recv"),
    "partial_recv": ("partial_recv", "distributed.collective.partial_recv"),
    "partial_allgather": ("partial_allgather",
                          "distributed.collective.partial_allgather"),
    "c_identity": ("mp identity = sharding annotation",
                   "parallel.mp_layers.mark_sharding"),
    "c_concat": ("concat", "concat"),
    "c_split": ("split", "split"),
    "c_embedding": ("VocabParallelEmbedding",
                    "parallel.mp_layers.VocabParallelEmbedding"),
    "embedding_with_scaled_gradient": ("F.embedding",
                                       "nn.functional.embedding"),
    "embedding_grad_add_to": ("F.embedding", "nn.functional.embedding"),
    "embedding_sparse": ("F.embedding", "nn.functional.embedding"),
    "sparse_weight_embedding": ("F.embedding", "nn.functional.embedding"),
    "bce_loss": ("F.binary_cross_entropy",
                 "nn.functional.binary_cross_entropy"),
    "kldiv_loss": ("F.kl_div", "nn.functional.kl_div"),
    "bicubic_interp": ("F.interpolate", "nn.functional.interpolate"),
    "bilinear_interp": ("F.interpolate", "nn.functional.interpolate"),
    "nearest_interp": ("F.interpolate", "nn.functional.interpolate"),
    "linear_interp": ("F.interpolate", "nn.functional.interpolate"),
    "trilinear_interp": ("F.interpolate", "nn.functional.interpolate"),
    "bilinear_tensor_product": ("F.bilinear", "nn.functional.bilinear"),
    "check_finite_and_unscale": ("amp.GradScaler (XLA-fused)",
                                 "amp.GradScaler"),
    "update_loss_scaling": ("amp.GradScaler", "amp.GradScaler"),
    "depthwise_conv2d": ("F.conv2d(groups=C)", "nn.functional.conv2d"),
    "depthwise_conv2d_transpose": ("F.conv2d_transpose(groups=C)",
                                   "nn.functional.conv2d_transpose"),
    "elementwise_pow": ("pow", "pow"),
    "elementwise_heaviside": ("heaviside", "heaviside"),
    "fft_c2c": ("fft.fft", "fft.fft"),
    "fft_c2r": ("fft.irfft", "fft.irfft"),
    "fft_r2c": ("fft.rfft", "fft.rfft"),
    "frobenius_norm": ("linalg.norm", "linalg.norm"),
    "full_batch_size_like": ("full_like", "full_like"),
    "gaussian": ("randn", "randn"),
    "truncated_gaussian_random": ("nn.initializer.TruncatedNormal",
                                  "nn.initializer.TruncatedNormal"),
    "graph_sample_neighbors": ("geometric.sample_neighbors",
                               "geometric.sample_neighbors"),
    "matrix_rank_tol": ("linalg.matrix_rank", "linalg.matrix_rank"),
    "max_pool2d_with_index": ("F.max_pool2d(return_mask=True)",
                              "nn.functional.max_pool2d"),
    "max_pool3d_with_index": ("F.max_pool3d", "nn.functional.max_pool3d"),
    "maxpool": ("F.max_pool2d", "nn.functional.max_pool2d"),
    "negative": ("neg", "neg"),
    "p_norm": ("linalg.norm", "linalg.norm"),
    "pad3d": ("F.pad", "nn.functional.pad"),
    "pool2d": ("F.avg_pool2d/max_pool2d", "nn.functional.avg_pool2d"),
    "pool3d": ("F.avg_pool3d/max_pool3d", "nn.functional.avg_pool3d"),
    "repeat_interleave_with_tensor_index": ("repeat_interleave",
                                            "repeat_interleave"),
    "rnn": ("nn.SimpleRNN/LSTM/GRU (lax.scan)", "nn.LSTM"),
    "segment_pool": ("geometric.segment_sum/mean/min/max",
                     "geometric.segment_sum"),
    "set_value_with_tensor": ("Tensor.set_value", "Tensor.set_value"),
    "sgd_sparse_param_sparse_grad": ("optimizer.SGD", "optimizer.SGD"),
    "split_with_num": ("split", "split"),
    "tril_triu": ("tril/triu", "tril"),
    "uniform_inplace": ("uniform", "uniform"),
    "unpool": ("F.max_unpool2d", "nn.functional.max_unpool2d"),
    "assign_value": ("assign", "assign"),
    "coo_to_csr": ("SparseCooTensor.to_sparse_csr",
                   "sparse.SparseCooTensor.to_sparse_csr"),
    "csr_to_coo": ("SparseCsrTensor.to_sparse_coo",
                   "sparse.SparseCsrTensor.to_sparse_coo"),
    "coo_to_dense": ("SparseCooTensor.to_dense",
                     "sparse.SparseCooTensor.to_dense"),
    "csr_to_dense": ("SparseCsrTensor.to_dense",
                     "sparse.SparseCsrTensor.to_dense"),
    "dense_to_coo": ("sparse.sparse_coo_tensor", "sparse.sparse_coo_tensor"),
    "dense_to_csr": ("sparse.sparse_csr_tensor", "sparse.sparse_csr_tensor"),
    "values_coo": ("SparseCooTensor.values", "sparse.SparseCooTensor.values"),
    "values_csr": ("SparseCsrTensor.values", "sparse.SparseCsrTensor.values"),
    "indices_coo": ("SparseCooTensor.indices",
                    "sparse.SparseCooTensor.indices"),
    "divide_scalar": ("sparse.divide", "sparse.divide"),
    "determinant": ("linalg.det", "linalg.det"),
    "spectral_norm": ("nn.utils.spectral_norm", "nn.utils.spectral_norm"),
    "identity_loss": ("incubate.identity_loss", "incubate.identity_loss"),
    "fill_diagonal_tensor": ("fill_diagonal_tensor", "fill_diagonal_tensor"),
    "decode_jpeg": ("vision.ops.decode_jpeg", "vision.ops.decode_jpeg"),
    "crop": ("crop", "crop"),
    "average_accumulates": ("incubate.optimizer.ModelAverage",
                            "incubate.optimizer.ModelAverage"),
    # reference DGC (deep gradient compression) family: this build's
    # gradient compression is the block-scaled int8 quantized sync with
    # error feedback (distributed/compress.py) — same role (cut grad
    # comm bytes on bandwidth-poor links), different algorithm
    "dgc": ("distributed.compress (quantized grad sync)",
            "distributed.compress.sync_gradients_compressed"),
    "dgc_momentum": ("distributed.compress error feedback",
                     "distributed.compress.reduce_grads_traced"),
    # the quantize/dequantize primitives behind it
}

# ops this build ADDS with no reference PHI kernel (the coverage audit
# runs reference->ours; these are the other direction, listed in the
# report so they stay visible and their targets rot-gated the same way)
BEYOND_REFERENCE = [
    ("quantize_int8_block", "block-scaled int8 gradient quantize "
     "(distributed compress wire/step payload)",
     "kernels.quant.quantize_int8_block"),
    ("dequantize_int8_block", "inverse of quantize_int8_block",
     "kernels.quant.dequantize_int8_block"),
    ("mla_decode", "absorbed latent-attention decode over latent pages "
     "(one shared row a token for every head)",
     "serving.kernels.mla_attention.mla_attention"),
    ("ssm_decode", "Mamba-2 decode step over slot state (a slot's float32 "
     "state read once and written once, in place)",
     "serving.kernels.ssm.ssm_decode"),
]


def resolve_alias(target):
    """Resolve a REF_TO_OURS target ('a.b.C.attr' under paddle_tpu, or
    'Tensor.method') to a live object; returns None if it no longer
    exists. Submodules not imported by the package root are imported on
    demand."""
    import importlib
    import types

    if target.startswith("Tensor."):
        import paddle_tpu

        obj = paddle_tpu.Tensor
        parts = target.split(".")[1:]
    else:
        obj = importlib.import_module("paddle_tpu")
        parts = target.split(".")
    for part in parts:
        nxt = getattr(obj, part, None)
        if nxt is None and isinstance(obj, types.ModuleType):
            try:
                nxt = importlib.import_module(obj.__name__ + "." + part)
            except ImportError:
                return None
        if nxt is None:
            return None
        obj = nxt
    return obj

def reference_kernel_names(ref):
    out = subprocess.run(
        ["grep", "-rhoP", r"PD_REGISTER_KERNEL(_FOR_ALL_DTYPE)?\(\s*\K\w+",
         os.path.join(ref, "paddle/phi/kernels")],
        capture_output=True, text=True)
    names = set(out.stdout.split())
    return names


def our_op_names():
    sys.path.insert(0, REPO)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import paddle_tpu  # noqa: F401
    from paddle_tpu.core.dispatch import OPS

    names = set(OPS)
    # public functional / tensor namespaces count as capabilities too
    import paddle_tpu.nn.functional as F
    import paddle_tpu.sparse as sparse
    from paddle_tpu.core.tensor import Tensor

    import paddle_tpu.metric
    import paddle_tpu.optimizer
    import paddle_tpu.vision.ops as vops

    mods = [F, paddle_tpu, sparse, paddle_tpu.linalg, paddle_tpu.fft,
            paddle_tpu.signal, paddle_tpu.geometric, paddle_tpu.metric,
            paddle_tpu.optimizer, vops, paddle_tpu.incubate.nn.functional
            if hasattr(paddle_tpu.incubate.nn, "functional")
            else paddle_tpu.incubate.nn]
    for mod in mods:
        names |= {n for n in dir(mod) if not n.startswith("_")}
    names |= {n for n in dir(Tensor) if not n.startswith("_")}
    return names


_SUFFIXES = [
    "_double_grad", "_triple_grad", "_grad_grad", "_grad", "_raw", "_sr",
    "_array", "_dense_param_sparse_grad", "_coo_coo", "_csr_csr",
    "_coo_dense", "_csr_dense", "_csr_coo", "_dense_coo", "_coo", "_csr",
    "_dense", "_intermediate", "_with_kernel", "_infer",
]


def strip_variants(name):
    """Peel backend/layout/autodiff suffixes: `add_coo_coo_grad` -> `add`,
    `adamw_dense_param_sparse_grad` -> `adamw`, `max_raw` -> `max`."""
    changed = True
    while changed:
        changed = False
        # longest-first so "_dense_param_sparse_grad" wins over "_grad"
        for s in sorted(_SUFFIXES, key=len, reverse=True):
            if name.endswith(s) and len(name) > len(s):
                name = name[:-len(s)]
                changed = True
    return name


def normalize(name):
    return name.lower()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reference", default="/root/reference")
    args = ap.parse_args()

    ref_names = reference_kernel_names(args.reference)
    ours = {normalize(n) for n in our_op_names()}
    alias_cover = dict(REF_TO_OURS)

    covered, via_alias, na, missing = [], [], [], []
    for name in sorted(ref_names):
        base = strip_variants(name)
        # grad-only strip too: full variant stripping can eat real name
        # parts ("coo_to_dense_grad" -> "coo_to"), so check both forms
        g = name
        for s in ("_double_grad", "_triple_grad", "_grad_grad", "_sparse_grad", "_grad"):
            while g.endswith(s) and len(g) > len(s):
                g = g[:-len(s)]
        base2 = base[len("sparse_"):] if base.startswith("sparse_") else base
        forms = (name, g, base, base2)
        if any(c in ours for c in forms):
            covered.append(name)
        elif any(c in alias_cover for c in forms):
            key = next(c for c in forms if c in alias_cover)
            disp, target = alias_cover[key]
            via_alias.append((name, disp, target))
        elif any(c in NA_BY_DESIGN for c in forms):
            na.append((name, next(NA_BY_DESIGN[c] for c in forms
                                  if c in NA_BY_DESIGN)))
        else:
            missing.append(name)

    total = len(ref_names)
    lines = []
    lines.append("# OP COVERAGE — reference PHI kernels vs paddle_tpu\n")
    lines.append("Generated by `tools/op_coverage.py`. Reference: %d "
                 "registered kernel names (`paddle/phi/kernels/`, "
                 "PD_REGISTER_KERNEL).\n" % total)
    lines.append("| bucket | count |")
    lines.append("|---|---|")
    lines.append("| covered (same name) | %d |" % len(covered))
    lines.append("| covered (alias) | %d |" % len(via_alias))
    lines.append("| n/a by design (CUDA/fluid artifact) | %d |" % len(na))
    lines.append("| missing | %d |" % len(missing))
    pct = 100.0 * (len(covered) + len(via_alias) + len(na)) / total
    lines.append("\n**Accounted: %.1f%%**\n" % pct)
    lines.append("## Missing (%d)\n" % len(missing))
    lines.append(", ".join("`%s`" % m for m in missing) or "(none)")
    # every alias target must resolve to a live object (rot gate; also
    # enforced by tests/test_op_coverage.py)
    unresolved = sorted({t for _, _, t in via_alias
                         if resolve_alias(t) is None})
    lines.append("\n## Covered via alias (%d)\n" % len(via_alias))
    lines.append("\n".join(
        "- `%s` -> %s (`paddle_tpu.%s`)" % (a, d, t)
        for a, d, t in via_alias))
    lines.append("\n## n/a by design (%d)\n" % len(na))
    lines.append("\n".join("- `%s` — %s" % (a, b) for a, b in na))
    unresolved += sorted({t for _, _, t in BEYOND_REFERENCE
                          if resolve_alias(t) is None})
    lines.append("\n## Beyond reference (%d)\n" % len(BEYOND_REFERENCE))
    lines.append("Ops this build adds with no reference PHI kernel "
                 "(rot-gated like aliases):\n")
    lines.append("\n".join(
        "- `%s` — %s (`paddle_tpu.%s`)" % (a, d, t)
        for a, d, t in BEYOND_REFERENCE))
    lines.append("\n> Note: the old manual \"metrics documented?\" "
                 "checklist item is superseded by ptlint's "
                 "metric-registry pass (`python tools/ptlint.py "
                 "--rules metric`), which machine-checks that every "
                 "registered metric is literal, family-prefixed, "
                 "label-consistent, and documented in README/BASELINE.")
    report = "\n".join(lines) + "\n"
    with open(os.path.join(REPO, "OP_COVERAGE.md"), "w") as f:
        f.write(report)
    print("missing=%d covered=%d alias=%d na=%d (accounted %.1f%%)"
          % (len(missing), len(covered), len(via_alias), len(na), pct))
    print("\n".join(missing))
    if unresolved:
        print("UNRESOLVED alias targets: %s" % unresolved)
        sys.exit(1)


if __name__ == "__main__":
    main()
