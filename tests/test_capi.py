"""C inference API e2e (reference inference/capi_exp/): save an
inference model, compile a real C program against pt_capi.h /
libpaddle_tpu_capi.so, run it as a separate process, and check its
output against the Python predictor.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.static as static

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

C_DRIVER = r"""
#include <stdio.h>
#include <stdlib.h>
#include "pt_capi.h"

int main(int argc, char** argv) {
  void* p = pt_predictor_create(argv[1]);
  if (!p) return 2;
  if (pt_predictor_num_inputs(p) != 1) return 3;
  float in[8];
  for (int i = 0; i < 8; ++i) in[i] = (float)i;
  int64_t shape[2] = {2, 4};
  pt_tensor_copy_from_cpu_float(p, pt_predictor_input_name(p, 0), in,
                                shape, 2);
  if (pt_predictor_run(p) != 0) return 4;
  const char* out_name = pt_predictor_output_name(p, 0);
  int nd = pt_tensor_ndim(p, out_name);
  int64_t oshape[8];
  pt_tensor_shape(p, out_name, oshape);
  long total = 1;
  for (int i = 0; i < nd; ++i) total *= oshape[i];
  float* out = (float*)malloc(total * sizeof(float));
  pt_tensor_copy_to_cpu_float(p, out_name, out);
  for (long i = 0; i < total; ++i) printf("%.6f\n", out[i]);
  free(out);
  pt_predictor_destroy(p);
  return 0;
}
"""


@pytest.mark.skipif(not os.path.exists(
    os.path.join(REPO, "paddle_tpu", "lib", "libpaddle_tpu_capi.so")),
    reason="capi lib not built")
class TestCAPI:
    def test_c_program_matches_python_predictor(self, tmp_path):
        # 1) save a tiny inference model
        paddle.seed(0)
        static.enable_static()
        main, startup = static.Program(), static.Program()
        with static.program_guard(main, startup):
            x = static.data("x", [2, 4], "float32")
            lin = nn.Linear(4, 3)
            y = lin(x).tanh()
        exe = static.Executor()
        exe.run(startup)
        prefix = str(tmp_path / "m")
        static.save_inference_model(prefix, [x], [y], exe, program=main)
        static.disable_static()

        # python-side expected output
        import paddle_tpu.inference as inf

        pred = inf.create_predictor(inf.Config(prefix))
        xin = np.arange(8, dtype=np.float32).reshape(2, 4)
        (want,) = pred.run([xin])

        # 2) compile the C driver
        cdir = tmp_path
        csrc = cdir / "driver.c"
        csrc.write_text(C_DRIVER)
        exe_path = str(cdir / "driver")
        libdir = os.path.join(REPO, "paddle_tpu", "lib")
        r = subprocess.run(
            ["gcc", "-o", exe_path, str(csrc),
             "-I", os.path.join(REPO, "csrc"),
             "-L", libdir, "-lpaddle_tpu_capi",
             "-Wl,-rpath," + libdir],
            capture_output=True, text=True)
        assert r.returncode == 0, r.stderr

        # 3) run it in a clean process (the embedded interpreter must
        #    find paddle_tpu and use the CPU backend)
        env = dict(os.environ)
        env.update({"PYTHONPATH": REPO + os.pathsep
                    + env.get("PYTHONPATH", ""),
                    "JAX_PLATFORMS": "cpu"})
        out = subprocess.run([exe_path, prefix], env=env,
                             capture_output=True, text=True, timeout=240)
        assert out.returncode == 0, (out.stdout[-800:], out.stderr[-1500:])
        got = np.array([float(l) for l in out.stdout.split()],
                       np.float32).reshape(want.shape)
        np.testing.assert_allclose(got, np.asarray(want), rtol=1e-5,
                                   atol=1e-6)


@pytest.mark.skipif(not os.path.exists(
    os.path.join(REPO, "paddle_tpu", "lib", "libpaddle_tpu_capi.so")),
    reason="capi lib not built")
class TestCppJitLayer:
    CPP = r"""
#include <cstdio>
#include "pt_jit.h"
int main(int argc, char** argv) {
  auto layer = paddle_tpu::jit::Load(argv[1]);
  paddle_tpu::jit::Tensor in;
  in.shape = {2, 4};
  for (int i = 0; i < 8; ++i) in.data.push_back((float)i);
  auto outs = layer.Forward({in});
  for (float v : outs[0].data) printf("%.6f\n", v);
  return 0;
}
"""

    def test_cpp_layer_matches_python(self, tmp_path):
        import paddle_tpu.inference as inf

        paddle.seed(0)
        static.enable_static()
        main, startup = static.Program(), static.Program()
        with static.program_guard(main, startup):
            x = static.data("x", [2, 4], "float32")
            y = nn.Linear(4, 3)(x).tanh()
        exe = static.Executor()
        exe.run(startup)
        prefix = str(tmp_path / "m")
        static.save_inference_model(prefix, [x], [y], exe, program=main)
        static.disable_static()
        pred = inf.create_predictor(inf.Config(prefix))
        xin = np.arange(8, dtype=np.float32).reshape(2, 4)
        (want,) = pred.run([xin])

        src = tmp_path / "drv.cc"
        src.write_text(self.CPP)
        exe_path = str(tmp_path / "drv")
        libdir = os.path.join(REPO, "paddle_tpu", "lib")
        r = subprocess.run(
            ["g++", "-std=c++17", "-o", exe_path, str(src),
             "-I", os.path.join(REPO, "csrc"),
             "-L", libdir, "-lpaddle_tpu_capi",
             "-Wl,-rpath," + libdir],
            capture_output=True, text=True)
        assert r.returncode == 0, r.stderr
        env = dict(os.environ)
        env.update({"PYTHONPATH": REPO + os.pathsep
                    + env.get("PYTHONPATH", ""),
                    "JAX_PLATFORMS": "cpu"})
        out = subprocess.run([exe_path, prefix], env=env,
                             capture_output=True, text=True, timeout=240)
        assert out.returncode == 0, (out.stdout[-500:], out.stderr[-1500:])
        got = np.array([float(l) for l in out.stdout.split()],
                       np.float32).reshape(np.asarray(want).shape)
        np.testing.assert_allclose(got, np.asarray(want), rtol=1e-5,
                                   atol=1e-6)
