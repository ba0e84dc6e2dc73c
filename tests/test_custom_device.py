"""Custom-device plugin path (VERDICT r2 #7; reference
phi/backends/custom/fake_cpu_device.h + custom_device_test.cc): register
a fake PJRT backend under its own platform name, point set_device at it,
and run a real train step on the plugged backend.

Runs in a subprocess: plugin registration must precede any jax backend
initialization (frozen at first use — same constraint as the reference's
dlopen-at-framework-init), and the pytest process has long since
initialized the CPU backend.
"""
from __future__ import annotations

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = r"""
import sys
sys.path.insert(0, %r)

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.core.place import CustomPlace, register_fake_cpu_device

# 1. register BEFORE first backend use (the plugin-discovery contract)
place = register_fake_cpu_device("fake_cpu")
assert isinstance(place, CustomPlace)
assert paddle.device.get_all_custom_device_type() == ["fake_cpu"]
assert paddle.device.is_compiled_with_custom_device("fake_cpu")

# 2. set_device resolves the plugged backend's own devices
p = paddle.device.set_device("fake_cpu:0")
assert p.device_type == "custom:fake_cpu", p.device_type
import jax
dev = p.jax_device()
assert dev in jax.devices("fake_cpu"), (dev, jax.devices("fake_cpu"))

# 3. one real train step entirely on the plugged backend
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F

paddle.seed(0)
model = nn.Linear(4, 2)
model.to(device="fake_cpu:0")
for prm in model.parameters():
    assert list(prm._value.devices())[0] in jax.devices("fake_cpu")
opt = paddle.optimizer.SGD(learning_rate=0.1,
                           parameters=model.parameters())
x = paddle.to_tensor(
    np.random.RandomState(0).randn(8, 4).astype(np.float32)).to(
        device="fake_cpu:0")
y = paddle.to_tensor(
    np.random.RandomState(1).randn(8, 2).astype(np.float32)).to(
        device="fake_cpu:0")
losses = []
for _ in range(5):
    loss = F.mse_loss(model(x), y)
    loss.backward()
    opt.step()
    opt.clear_grad()
    losses.append(float(loss))
assert losses[-1] < losses[0], losses
for prm in model.parameters():
    assert list(prm._value.devices())[0] in jax.devices("fake_cpu")
print("CUSTOM_DEVICE_OK", losses[0], losses[-1])
""" % REPO


def test_fake_pjrt_device_runs_train_step():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    # allow both the default cpu platform and the plugged one
    env["JAX_PLATFORMS"] = "cpu,fake_cpu"
    proc = subprocess.run([sys.executable, "-c", WORKER], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "CUSTOM_DEVICE_OK" in proc.stdout, proc.stdout


def test_register_after_init_raises():
    import pytest

    from paddle_tpu.core.place import register_custom_device_factory

    # this pytest process initialized jax long ago: registration must
    # refuse loudly instead of silently never taking effect
    import jax

    jax.devices()
    with pytest.raises(RuntimeError, match="after the JAX runtime"):
        register_custom_device_factory("late_dev", lambda: None)
