"""A later PR adds files and entries and edits no file that is there.
Here a configuration, a mix, a layer metric and a ``workloads`` entry
are dropped into a temporary copy of the benchmark's directories, and
the harness finds and runs them by name with every existing file left
byte for byte as it was."""
import hashlib
import json
import os
import re
import shutil

import pytest

import run as bench
import tiny

NEW_METRIC = '''"""Steps the engine took in the window."""


def read(obs):
    steps = obs.get("steps", ())
    return float(len(steps)) if steps else None
'''


def digests(top):
    out = {}
    for folder, _, files in os.walk(top):
        for name in files:
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, top)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


@pytest.fixture()
def copy(tmp_path):
    here = tmp_path / "benchmark"
    shutil.copytree(bench.HERE, here, ignore=shutil.ignore_patterns(
        "tests", "__pycache__", ".trace"))
    shutil.copy(os.path.join(bench.ROOT, "BENCHMARK.json"), tmp_path)
    return tmp_path, here


def test_new_files_are_found_and_run(copy):
    root, here = copy
    before = digests(here)

    (here / "configs" / "tiny-mistral.json").write_text(
        json.dumps(tiny.CONFIG))
    (here / "traffic" / "tiny-backlog.json").write_text(json.dumps(
        dict(tiny.BACKLOG, runner="serve_backlog", kernels={})))
    (here / "layer_metrics" / "serve.steps.py").write_text(NEW_METRIC)
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "tiny-mistral", "source": "none: a test's size",
        "file": "benchmark/configs/tiny-mistral.json", "reduced": [],
        "why": "found by name"})
    manifest["workloads"].append({
        "name": "tiny.backlog", "config": "tiny-mistral",
        "traffic": "tiny-backlog", "chips": 1, "why": "found by name"})
    manifest["per_layer"].append({
        "name": "serve.steps", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "serve entry",
        "moves": "serve_out_tok_s", "workloads": ["tiny.backlog"]})
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in metric and metric["moves" if "moves" in metric
                                           else "name"] in (
                "serve_out_tok_s", "itl_p95_ms"):
            metric["workloads"].append("tiny.backlog")

    cell, config, traffic, family, runner = bench.resolve(
        manifest, "tiny.backlog", root=str(root), here=str(here))
    assert cell["traffic"] == "tiny-backlog" and config == tiny.CONFIG
    assert family.__file__ == str(here / "families" / "mistral.py")
    result = runner.run_backlog(family, config, traffic, tiny.SEED, 0.5,
                                tiny.quiet, on_chip=False)
    obs = dict(result["observations"], trace=None, config=config,
               traffic=traffic, family=family, chips=1, peaks={},
               log=tiny.quiet, end_to_end=result["end_to_end"])
    values = bench.read_layer_metrics(manifest, "tiny.backlog", obs,
                                      here=str(here))
    assert values["serve.steps"] == len(result["observations"]["steps"])
    assert "serve.decode_step_ms" in values
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 1}
    line = bench.result_line(manifest, "tiny.backlog", False, result, 1.0,
                             {}, device, None)
    assert set(line["metrics"]) == {"serve_out_tok_s", "itl_p95_ms",
                                    "setup_s"}
    assert line["correct"] is True

    after = digests(here)
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == [
        "configs/tiny-mistral.json", "layer_metrics/serve.steps.py",
        "traffic/tiny-backlog.json"]


def test_a_name_with_no_file_says_so(copy):
    root, here = copy
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    with pytest.raises(KeyError, match="no workload named"):
        bench.resolve(manifest, "nope", root=str(root), here=str(here))
    manifest["per_layer"].append({"name": "not.there", "unit": "x",
                                  "better": "higher"})
    with pytest.raises(FileNotFoundError, match="not.there"):
        bench.read_layer_metrics(manifest, "any", {}, here=str(here))


def test_run_py_names_no_cell_config_family_mix_or_metric():
    manifest = bench.load_json(os.path.join(bench.ROOT, "BENCHMARK.json"))
    names = set()
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        names |= {entry["name"] for entry in manifest[section]}
    for cell in manifest["workloads"]:
        names |= {cell["traffic"]}
    for folder in ("families", "runners", "traffic", "configs",
                   "layer_metrics"):
        names |= {os.path.splitext(f)[0]
                  for f in os.listdir(os.path.join(bench.HERE, folder))
                  if not f.startswith("_")}
    names.discard("setup_s")    # the one metric the harness takes itself
    with open(os.path.join(bench.HERE, "run.py")) as f:
        source = f.read()
    found = [n for n in sorted(names)
             if re.search(r"(?<![\w.-])%s(?![\w-])" % re.escape(n), source)]
    assert found == []


def test_manifest_points_at_files_that_exist():
    manifest = bench.load_json(os.path.join(bench.ROOT, "BENCHMARK.json"))
    end_to_end = {m["name"]: m for m in manifest["end_to_end"]}
    cells = {w["name"] for w in manifest["workloads"]}
    for cell in manifest["workloads"]:
        bench.resolve(manifest, cell["name"])
    for metric in manifest["per_layer"]:
        path = os.path.join(bench.HERE, "layer_metrics",
                            metric["name"] + ".py")
        assert os.path.isfile(path), path
        moved = end_to_end[metric["moves"]]
        # every cell that reports this metric reports what it moves
        assert set(metric.get("workloads", cells)) <= set(
            moved.get("workloads", cells))
    for config in manifest["configs"]:
        body = bench.load_json(os.path.join(bench.ROOT, config["file"]))
        assert set(config["reduced"]) == set(body["reduced"])
