"""One measurement story.

A number about this system's speed comes from ``benchmark/run.py`` on
the chip and is written in ``PERF_LEDGER.jsonl`` / ``PERF.md``.
``benchmark/`` has to stand alone (its files are the yardstick), so two
things exist twice and are held to one answer here and in
``tests/test_tpu_lowering.py`` (the Mosaic kernel finders):

- the benchmark measures on a TPU or not at all;
- ``benchmark/peaks.py`` and ``DEVICE_PEAKS`` agree on the v5e chip;
- the README's bench section names the benchmark's cells and no file
  that is gone.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_benchmark_without_a_tpu_no_result_line_no_file(tmp_path):
    """After benchmark/tests/test_runners.py::test_no_accelerator_no_result
    (which tier-1 does not run)."""
    def entries():
        return {d: sorted(e for e in os.listdir(os.path.join(REPO, d))
                          if e != "__pycache__")
                for d in (".", "benchmark")}

    before = entries()
    done = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", "mistral7b-pretrain-4k", "--seed", "1",
         "--seconds", "1"],
        cwd=str(tmp_path), capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=300)
    assert done.returncode != 0, done.stdout + done.stderr
    assert "{" not in done.stdout
    assert "chip only" in done.stderr
    assert os.listdir(str(tmp_path)) == []
    assert entries() == before


def test_both_peak_tables_agree_on_the_v5e_chip():
    from paddle_tpu.distributed.auto_parallel.cost_model import DEVICE_PEAKS

    spec = importlib.util.spec_from_file_location(
        "benchmark_peaks", os.path.join(REPO, "benchmark", "peaks.py"))
    peaks = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(peaks)
    kind = "TPU v5 lite"     # what the chip reports as its device_kind
    ours, theirs = DEVICE_PEAKS[kind], peaks.PEAKS[kind]
    assert ours["peak_flops"] == theirs["flops_bf16"]
    assert ours["hbm_bw"] == theirs["hbm_bytes_s"]


def test_readme_bench_section_names_every_cell_and_no_missing_file():
    with open(os.path.join(REPO, "README.md")) as f:
        readme = f.read()
    section = readme.split("\n## Tests / bench\n", 1)[1].split("\n## ", 1)[0]
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        cells = [w["name"] for w in json.load(f)["workloads"]]
    assert cells
    for cell in cells:
        assert "`%s`" % cell in section, cell
    # a path: a word in backticks with a file suffix, or with a slash
    # under a directory of the checkout (`tokens/s` is neither)
    roots = {e for e in os.listdir(REPO)
             if os.path.isdir(os.path.join(REPO, e))}
    named = set()
    for span in re.findall(r"`([^`]+)`", section):
        for word in span.split():
            word = word.strip("()[],;:")
            if not re.fullmatch(r"[\w.-]+(/[\w.-]+)*/?", word):
                continue
            if re.search(r"\.(py|json|jsonl|md|toml)$", word) or (
                    "/" in word and word.split("/", 1)[0] in roots):
                named.add(word)
    assert {"benchmark/run.py", "BENCHMARK.json"} <= named, named
    missing = sorted(p for p in named
                     if not os.path.exists(os.path.join(REPO, p)))
    assert missing == []


def _load_benchmark_module(name):
    """``benchmark/<name>.py`` by path; its directory is importable while
    it loads (``scope_time`` imports ``trace_reduce`` as ``run.py`` lets
    it)."""
    here = os.path.join(REPO, "benchmark")
    sys.path.insert(0, here)
    try:
        spec = importlib.util.spec_from_file_location(
            "benchmark_" + name, os.path.join(here, name + ".py"))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module
    finally:
        sys.path.remove(here)


def test_scope_time_reads_scopes_and_kernels_as_the_program_writes_them():
    """``benchmark/scope_time.py`` stands alone, so it has its own copy
    of ``hlo.scope_of`` and a table of the Mosaic kernels' classes: both
    are held to the program here, as ``trace_reduce.kernel_instructions``
    is to ``mosaic_kernels`` in tests/test_tpu_lowering.py."""
    from paddle_tpu.analysis.graph import hlo

    scope_time = _load_benchmark_module("scope_time")
    assert scope_time.SCOPE_KINDS == hlo.SCOPE_KINDS
    assert scope_time.MODEL_SCOPES == hlo.MODEL_SCOPES
    kinds = hlo.SCOPE_KINDS + hlo.MODEL_SCOPES
    assert set(scope_time.CLASS_OF_KIND) == set(kinds)
    assert set(scope_time.CLASS_OF_KIND.values()) | {scope_time.UNNAMED} \
        == set(scope_time.CLASSES)
    forms = ["jit(f)/%s/dot", "jit(f)/jvp(%s)/add", "%s",
             "jit(step)/transpose(jvp(%s))/mul",
             "jit(step)/transpose(jvp(jvp()))/checkpoint/"
             "rematted_computation/%s/while/body/closed_call/tanh",
             "jit(f)/x%s/dot", "jit(f)/%s_y/dot", "jit(f)/cond/"
             "branch_1_fun/%s/jit(argsort)/sort"]
    names = ["layer_%d/%s" % (i, k) for i, k in enumerate(kinds)] \
        + ["layer_7)/jvp(layer_7)/checkpoint/" + k for k in kinds] \
        + list(kinds) + ["layer_3", "layer_2/mul/attn", "attn", ""]
    for form in forms:
        for name in names:
            op_name = form % name
            assert scope_time.scope_of(op_name) == hlo.scope_of(op_name), \
                op_name
    # the kernel table names every pallas_call the program has, by the
    # string its ``name=`` carries, and nothing else
    in_program = set()
    for root, _, files in os.walk(os.path.join(REPO, "paddle_tpu")):
        for file in files:
            if file.endswith(".py"):
                with open(os.path.join(root, file)) as f:
                    in_program |= set(re.findall(
                        r'^\s+name="(\w+)",?$', f.read(), re.M))
    served = {"flash_fwd", "flash_dq", "flash_dkv", "paged_decode",
              "paged_mixed", "mla_decode", "moe_gmm", "ssm_decode"}
    assert set(scope_time.CLASS_OF_KERNEL) == served <= in_program
    for kernel, cls in scope_time.CLASS_OF_KERNEL.items():
        assert cls in scope_time.CLASSES
        wrapped = "jit(step)/transpose(jvp(%s))/pallas_call" % kernel
        for op_name in ("jit(f)/%s/pallas_call" % kernel, wrapped,
                        "jit(f)/jit(_wrapper)/%s/pallas_call" % kernel):
            assert scope_time.kernel_of(op_name) == kernel
            assert hlo.mosaic_kernels(
                '%%c = f32[] custom-call(), custom_call_target='
                '"tpu_custom_call", metadata={op_name="%s"}'
                % op_name) == {kernel: 1}
    assert scope_time.kernel_of("jit(f)/layer_0/attn/dot_general") is None
    assert scope_time.kernel_of("jit(f)/other_kernel/pallas_call") is None
