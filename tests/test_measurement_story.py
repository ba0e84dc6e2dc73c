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
