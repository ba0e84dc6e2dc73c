"""chip_smoke.py off the chip: its phase functions at a tiny size on the
CPU (kernels in interpret mode, ``on_chip=False``), and the script
itself refusing to run anywhere but on a TPU.

The tiny geometry lives HERE: chip_smoke.py has no CPU configuration.
"""
from __future__ import annotations

import os
import shutil
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke as cs  # noqa: E402

# batch * seq == 256 == fused_ce.DEFAULT_BLOCK_T, so the train_fused_ce
# phase really takes the streaming kernel (interpreted)
TINY = cs.FULL._replace(
    hidden=64, intermediate=128, layers=2, heads=4, vocab=256,
    max_pos=256, dtype="float32", batch=2, seq=128,
    slots=4, num_blocks=64, block_size=8,
    prompt_lens=(5, 12, 24, 30), parity_request=2, new_tokens=12,
    shared_prefix=16, suffix_lens=(4, 9, 17, 20))


@pytest.fixture(scope="module")
def train_out():
    return cs.phase_train(TINY, jax.devices(), keep_init=True,
                          on_chip=False)


class TestPhasesAtTinySize:
    def test_train_loss_falls_on_one_device(self, train_out):
        losses, init = train_out
        assert len(losses) == 4 and losses[-1] < losses[0]
        assert "llama.embed_tokens.weight" in init

    def test_train_fused_ce_takes_the_kernel_and_matches(
            self, train_out, monkeypatch):
        from paddle_tpu.kernels import fused_ce

        calls = []
        real = fused_ce.fused_mean_ce
        monkeypatch.setattr(
            fused_ce, "fused_mean_ce",
            lambda *a: calls.append(1) or real(*a))
        losses, _ = cs.phase_train(TINY, jax.devices(), fused_ce=True,
                                   steps=2, ref_loss0=train_out[0][0],
                                   on_chip=False)
        assert calls and len(losses) == 3
        # the flag is restored for whoever runs next
        import paddle_tpu as paddle

        assert paddle.get_flags("FLAGS_fused_lm_head_ce")[
            "FLAGS_fused_lm_head_ce"] is False

    def test_loss_mismatch_fails_the_phase(self, train_out):
        with pytest.raises(AssertionError, match="differs from unfused"):
            cs.check_loss0("unfused", train_out[0][0],
                           train_out[0][0] * 1.02)

    def test_serve(self):
        cs.phase_serve(TINY, on_chip=False)

    def test_serve_mixed(self):
        cs.phase_serve(TINY, mixed=True, on_chip=False)

    def test_serve_mla(self):
        cs.phase_serve_mla(on_chip=False, dtype="float32")

    def test_serve_ssm(self):
        cs.phase_serve_ssm(on_chip=False, dtype="float32")

    def test_train4_shards_over_four_devices(self, train_out):
        from paddle_tpu.distributed import mesh as pmesh

        before = pmesh.current_mesh()
        losses = cs.phase_train4(TINY, jax.devices(), train_out[0][0],
                                 train_out[1], on_chip=False)
        assert losses[-1] < losses[0]
        assert pmesh.current_mesh() is before     # mesh restored

    def test_missing_kernel_fails_the_phase(self):
        with pytest.raises(AssertionError, match="lacks the Mosaic"):
            cs.require_kernels("ENTRY %main () -> f32[] {\n}",
                               ["flash_fwd"], "train step")


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


class TestScriptInsistsOnTheChip:
    def test_cpu_run_exits_nonzero_naming_the_platform(self, tmp_path):
        in_checkout = os.path.join(REPO, ".jax_compile_cache")
        before = (sorted(os.listdir(in_checkout))
                  if os.path.isdir(in_checkout) else None)
        cache = tmp_path / "cache"
        r = _run(REPO, {"JAX_COMPILATION_CACHE_DIR": str(cache)})
        assert r.returncode == 2, r.stdout + r.stderr
        assert "platform is 'cpu', not 'tpu'" in r.stdout
        assert "placed by JAX_COMPILATION_CACHE_DIR" in r.stdout
        # no result line, and no phase past `device` ran
        assert '"ok"' not in r.stdout
        assert "train: start" not in r.stdout
        # with the cache placed from outside, nothing appears under the
        # in-checkout path
        after = (sorted(os.listdir(in_checkout))
                 if os.path.isdir(in_checkout) else None)
        assert after == before

    def test_alone_in_a_directory_it_fails(self, tmp_path):
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
        env = {"PYTHONPATH": ""}
        r = _run(str(tmp_path), env)
        assert r.returncode != 0
        assert '"ok"' not in r.stdout
        assert "paddle_tpu" in r.stderr         # ModuleNotFoundError
