"""Nothing on the way to the chip may hide the device.

- the compile cache is placed from outside (core/compile_cache.py);
- an accelerator place never resolves to a CPU device or wraps its id;
- an MFU has a denominator only for a device kind with a sourced row;
- a launcher never starts several backend-owning processes on a TPU
  (one process per chip);
- the native library is rebuilt from csrc/ by source hash, not mtime;
- a step traces under its builder's mesh.
"""
from __future__ import annotations

import os

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestCompileCachePlacement:
    def test_env_set_means_no_config_write(self, monkeypatch, tmp_path):
        from paddle_tpu.core import compile_cache

        writes = []
        monkeypatch.setattr(jax.config, "update",
                            lambda *a, **k: writes.append(a))
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "cc"))
        assert compile_cache.configure() == str(tmp_path / "cc")
        assert writes == []

    def test_unset_means_the_fixed_in_checkout_path(self, monkeypatch):
        from paddle_tpu.core import compile_cache

        writes = []
        monkeypatch.setattr(jax.config, "update",
                            lambda *a, **k: writes.append(a))
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        got = compile_cache.configure()
        assert got == compile_cache.DEFAULT_DIR
        assert got == os.path.join(REPO, ".jax_compile_cache")
        assert writes == [
            ("jax_compilation_cache_dir", got),
            ("jax_persistent_cache_min_compile_time_secs", 0.0)]
        # fixed: a second call names the same directory
        assert compile_cache.configure() == got

    def test_default_dir_is_git_ignored(self):
        with open(os.path.join(REPO, ".gitignore")) as f:
            assert ".jax_compile_cache/" in f.read().split()

    def test_autotune_goes_through_the_helper(self, monkeypatch):
        from paddle_tpu.core import compile_cache
        from paddle_tpu.incubate import autotune

        calls = []
        monkeypatch.setattr(compile_cache, "configure",
                            lambda: calls.append(1))
        prev = autotune.get_config()
        try:
            autotune.set_config({"kernel": {"enable": True}})
        finally:
            autotune._config.update(prev)
        assert calls == [1]

    def test_entries(self, tmp_path):
        from paddle_tpu.core import compile_cache

        assert compile_cache.entries(str(tmp_path / "absent")) == []
        (tmp_path / "jit_step-abc-cache").write_text("x")
        (tmp_path / "jit_step-abc-atime").write_text("x")
        assert compile_cache.entries(str(tmp_path)) == [
            "jit_step-abc-cache"]


class TestPlacesDoNotFallBack:
    def test_tpu_place_without_an_accelerator_raises(self):
        import paddle_tpu as paddle

        with pytest.raises(RuntimeError, match="No tpu devices"):
            paddle.TPUPlace(0).jax_device()
        with pytest.raises(RuntimeError, match="No tpu devices"):
            paddle.CUDAPlace(0).jax_device()

    def test_out_of_range_id_raises_instead_of_wrapping(
            self, monkeypatch):
        from paddle_tpu.core import place

        monkeypatch.setattr(place, "_devices_by_type",
                            lambda kind: tuple(jax.devices()[:1]))
        assert place.TPUPlace(0).jax_device() is jax.devices()[0]
        with pytest.raises(RuntimeError, match="out of range"):
            place.TPUPlace(3).jax_device()
        with pytest.raises(RuntimeError, match="out of range"):
            place.TPUPlace(-1).jax_device()

    def test_cpu_place_still_resolves(self):
        import paddle_tpu as paddle

        assert paddle.CPUPlace().jax_device().platform == "cpu"


class TestMachineSpecNeedsAKnownKind:
    def test_unknown_kind_raises(self, monkeypatch):
        from paddle_tpu.monitor import perf

        for _, env in perf._PEAK_ENV:
            monkeypatch.delenv(env, raising=False)
        with pytest.raises(perf.UnknownDeviceKindError,
                           match="device kind 'cpu'"):
            perf.machine_spec()
        # and so does the code that divides by it
        with pytest.raises(perf.UnknownDeviceKindError):
            perf.bench_fields({"flops_per_step": 1e9},
                              tokens_per_s=10.0, tokens_per_step=5)
        with pytest.raises(perf.UnknownDeviceKindError):
            perf.TrainStepPerf("train")

    def test_explicit_denominator_or_override_works(self, monkeypatch,
                                                    cpu_peaks):
        from paddle_tpu.monitor import perf

        assert perf.machine_spec() == {
            "peak_flops": 197e12, "hbm_bw": 819e9, "ici_bw": 45e9}
        monkeypatch.setenv("PT_PERF_PEAK_FLOPS", "1e12")
        row = perf.bench_fields({"flops_per_step": 1e9},
                                tokens_per_s=10.0, tokens_per_step=5)
        assert row["mfu_peak_flops"] == 1e12 and row["mfu"] == 0.002
        for _, env in perf._PEAK_ENV:
            monkeypatch.delenv(env)
        row = perf.bench_fields({"flops_per_step": 1e9},
                                tokens_per_s=10.0, tokens_per_step=5,
                                peak_flops=2e12)
        assert row["mfu"] == 0.001

    def test_malformed_override_raises(self, monkeypatch, cpu_peaks):
        from paddle_tpu.monitor import perf

        monkeypatch.setenv("PT_PERF_HBM_BW", "fast")
        with pytest.raises(ValueError):
            perf.machine_spec()

    def test_v5e_row_is_keyed_by_the_kind_the_chip_reports(self):
        from paddle_tpu.distributed.auto_parallel.cost_model import (
            DEVICE_PEAKS,
            MachineSpec,
        )

        row = DEVICE_PEAKS["TPU v5 lite"]
        assert row["peak_flops"] == 197e12 and row["hbm_bw"] == 819e9
        # the planner's target machine reads the same row
        assert MachineSpec().peak_flops == row["peak_flops"]
        assert MachineSpec(peak_flops=1.0).peak_flops == 1.0

    def test_debugz_payload_names_the_kind_without_raising(
            self, monkeypatch):
        from paddle_tpu.monitor import perf

        for _, env in perf._PEAK_ENV:
            monkeypatch.delenv(env, raising=False)
        payload = perf.perf_payload()
        assert payload["device_kind"] == "cpu"
        assert payload["machine"] == {}


class TestOneProcessPerChip:
    def test_spawn_refuses_several_processes_on_a_tpu(self, monkeypatch):
        import paddle_tpu.distributed as dist

        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        ran = []
        with pytest.raises(RuntimeError, match="one process at a time"):
            dist.spawn(ran.append, args=(1,), nprocs=2)
        assert ran == []
        dist.spawn(ran.append, args=(1,), nprocs=1)     # in-process: fine
        assert ran == [1]

    def test_platform_env_that_rules_a_tpu_out_skips_the_backend(
            self, monkeypatch):
        import paddle_tpu.distributed as dist

        def boom():
            raise AssertionError("backend initialised")

        monkeypatch.setattr(jax, "default_backend", boom)
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        dist.refuse_multiprocess_on_tpu("x")           # no jax call
        monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
        with pytest.raises(AssertionError, match="backend initialised"):
            dist.refuse_multiprocess_on_tpu("x")

    def test_spawn_children_inherit_the_parents_platform(
            self, monkeypatch):
        import paddle_tpu.distributed as dist

        monkeypatch.setenv("JAX_PLATFORMS", "cpu,fake")
        # _spawn_worker writes the rank variables into os.environ (it
        # runs in the child); registering them here makes monkeypatch
        # put the old values back
        for var in ("PADDLE_TRAINER_ID", "PADDLE_LOCAL_RANK",
                    "PADDLE_TRAINERS_NUM"):
            monkeypatch.setenv(var, "0")
        dist._spawn_worker(lambda: None, (), 1, 2)
        assert os.environ["JAX_PLATFORMS"] == "cpu,fake"
        assert os.environ["PADDLE_TRAINER_ID"] == "1"

    def test_launcher_refuses_several_workers_on_a_tpu(self,
                                                       monkeypatch):
        from paddle_tpu.distributed.launch import controller as ctl

        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

        class Cfg:
            nproc_per_node = 2

        c = ctl.Controller.__new__(ctl.Controller)
        c.cfg = Cfg()
        with pytest.raises(RuntimeError, match="launch --nproc_per_node 2"):
            c.build_pod()


class TestNativeBuildBySourceHash:
    def test_hash_covers_sources_headers_and_flags(self, monkeypatch):
        from paddle_tpu.core import native

        h0 = native._source_hash()
        assert h0 == native._source_hash()
        monkeypatch.setattr(native, "_BUILD_FLAGS",
                            native._BUILD_FLAGS + ["-DX"])
        assert native._source_hash() != h0

    def test_loaded_library_matches_the_recorded_hash(self):
        from paddle_tpu.core import native

        native.get_lib()
        assert native._recorded_hash() == native._source_hash()

    def test_missing_or_different_hash_means_rebuild(self, monkeypatch,
                                                     tmp_path):
        """The decision get_lib() makes, on a copy of the tree whose
        mtimes mean nothing."""
        from paddle_tpu.core import native

        lib = tmp_path / "libpaddle_tpu_core.so"
        monkeypatch.setattr(native, "_LIB_PATH", str(lib))
        monkeypatch.setattr(native, "_HASH_PATH", str(lib) + ".srchash")
        monkeypatch.setattr(native, "_LIB", None)
        built = []
        monkeypatch.setattr(native, "_build",
                            lambda h: built.append(h) or _fake_build(
                                native, h))
        monkeypatch.setattr(native.ctypes, "CDLL", lambda p: object())
        monkeypatch.setattr(native, "_declare", lambda lib: lib)

        def load():
            monkeypatch.setattr(native, "_LIB", None)
            native.get_lib()

        load()                                  # no .so: build
        assert len(built) == 1
        load()                                  # hash matches: no build
        assert len(built) == 1
        (tmp_path / "libpaddle_tpu_core.so.srchash").write_text("old\n")
        load()                                  # hash differs: build
        assert len(built) == 2
        os.remove(str(lib) + ".srchash")
        load()                                  # hash missing: build
        assert len(built) == 3


def _fake_build(native, src_hash):
    with open(native._LIB_PATH, "w") as f:
        f.write("so")
    with open(native._HASH_PATH, "w") as f:
        f.write(src_hash + "\n")


class TestStepsTraceUnderTheirOwnMesh:
    def test_scoped_mesh_restores(self):
        from jax.sharding import Mesh

        from paddle_tpu.distributed import mesh as pmesh

        outer = pmesh.current_mesh()
        one = Mesh(np.array(jax.devices()[:1]), ("dp",))
        with pmesh.scoped_mesh(one):
            assert pmesh.get_mesh() is one
            with pytest.raises(RuntimeError):
                with pmesh.scoped_mesh(None):
                    assert pmesh.current_mesh() is None
                    raise RuntimeError("boom")
            assert pmesh.get_mesh() is one
        assert pmesh.current_mesh() is outer

    def test_train_step_traces_under_its_mesh_not_the_global_one(self):
        from jax.sharding import Mesh

        import paddle_tpu as paddle
        from paddle_tpu.distributed import mesh as pmesh
        from paddle_tpu.parallel.engine import CompiledTrainStep

        seen = []

        class Probe(paddle.nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc = paddle.nn.Linear(4, 4)

            def forward(self, x, labels):
                seen.append(pmesh.current_mesh())       # at trace time
                return ((self.fc(x) - labels) ** 2).mean()

        model = Probe()
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=model.parameters())
        one = Mesh(np.array(jax.devices()[:1]), ("dp",))
        step = CompiledTrainStep(model, None, opt, mesh=one,
                                 labels_to_model=True)
        x = np.ones((2, 4), np.float32)
        prev = pmesh.current_mesh()
        outer = pmesh.build_hybrid_mesh(dp=8)   # the process's mesh
        try:
            step(x, x)
            assert pmesh.current_mesh() is outer    # and put back
        finally:
            pmesh.set_mesh(prev)
        assert seen and all(m is one for m in seen)

    def test_engine_traces_under_a_one_device_mesh(self):
        import paddle_tpu as paddle
        from paddle_tpu import serving
        from paddle_tpu.distributed import mesh as pmesh
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.nn.functional import attention as att

        seen = []
        real = att._sdpa_reference

        def spy(*a, **k):
            seen.append(pmesh.current_mesh())
            return real(*a, **k)

        paddle.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny(use_parallel=False))
        eng = serving.Engine(model, max_slots=2, num_blocks=16,
                             block_size=8)
        prev = pmesh.current_mesh()
        pmesh.build_hybrid_mesh(dp=8)
        try:
            att._sdpa_reference = spy
            eng.add_request([1, 2, 3], max_new_tokens=2)
            eng.run()
        finally:
            att._sdpa_reference = real
            pmesh.set_mesh(prev)
        assert seen and all(m.size == 1 for m in seen)
