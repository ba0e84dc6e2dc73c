"""paddle.incubate.autotune — runtime tuning knobs.

Parity: reference python/paddle/incubate/autotune.py set_config(config)
with "kernel" (exhaustive cudnn algo search), "layout" (NCHW<->NHWC
autotune), "dataloader" (num_workers tuning) sections. TPU-native mapping:
- kernel  -> XLA's autotuner already picks MXU tilings per-compile; the
  knob turns on jax persistent compilation caching (placed by
  core/compile_cache.py) so tuned programs are reused across processes.
- layout  -> conv layouts: XLA on TPU canonicalizes internally; we record
  the preference for the conv lowering.
- dataloader -> tunes DataLoader prefetch depth.
"""
from __future__ import annotations

import json

_config = {
    "kernel": {"enable": False, "tuning_range": [1, 10]},
    "layout": {"enable": False},
    "dataloader": {"enable": False},
}


def set_config(config=None):
    """Accepts a dict or a path to a JSON file (reference autotune.py:24)."""
    global _config
    if config is None:
        for section in _config.values():
            section["enable"] = True
        _apply()
        return
    if isinstance(config, str):
        with open(config) as f:
            config = json.load(f)
    if not isinstance(config, dict):
        raise TypeError("set_config expects dict, JSON path, or None")
    for key, val in config.items():
        if key in _config and isinstance(val, dict):
            _config[key].update(val)
    _apply()


def get_config():
    return {k: dict(v) for k, v in _config.items()}


def _apply():
    if _config["kernel"]["enable"]:
        # persistent compilation cache = cross-process kernel reuse
        from ..core import compile_cache

        compile_cache.configure()
