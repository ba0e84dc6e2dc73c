#!/usr/bin/env python3
"""Logits through the serving cache against a benchmark family's plain
reference: prefill, then a few decode steps, compared row by row with
the reference's full forward over the same tokens.

    python3 tools/serving_parity.py --config <benchmark config name>
        [--prompts 512,384] [--steps 8] [--seed N]

``logits_through_cache`` is what ``Engine._prefill_fn`` and
``Engine._decode_fn`` compute before their argmax, through the engine's
own cache hooks (any model the engine serves with its split steps); the
tests call it at a tiny size. ``main`` runs it at a configuration's
published widths on whatever device JAX has (the chip, to mean
anything), and for a family whose reference reports its routing also
counts the top-k expert selections in which program and reference
differ: a flipped near-tie is rounding, not a fault, and the count says
how many there were. Prints one JSON line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def logits_through_cache(eng, tokens, steps, slot=1):
    """-> (float32 [steps + 1, vocab], bucket): the prefill's last real
    row over ``tokens[:-steps]`` (right-padded to its bucket), then one
    row a decode step fed ``tokens[-steps:]`` in turn, in slot
    ``slot`` of ``eng`` (its other slots idle)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.core.dispatch import no_grad
    from paddle_tpu.core.tensor import Tensor

    cache, model = eng.cache, eng.model
    p = len(tokens) - steps
    bucket = eng._bucket(p)
    if not cache.ensure_capacity(slot, len(tokens)):
        raise RuntimeError("the engine's pool cannot hold %d tokens"
                           % len(tokens))

    def prefill(state_vals, pools, ids, table_row, true_len):
        with model.bind_state(eng._names, list(state_vals)), no_grad():
            views = cache.prefill_views(pools, table_row, true_len)
            logits, views = model.generate_step(Tensor(ids), views, 0)
        return (logits._value[0, true_len - 1].astype(jnp.float32),
                [v.pool for v in views])

    def decode(state_vals, pools, toks, tables, lens):
        with model.bind_state(eng._names, list(state_vals)), no_grad():
            views = cache.decode_views(pools, tables, lens)
            logits, views = model.generate_step(Tensor(toks[:, None]),
                                                views, lens)
        return (logits._value[:, 0].astype(jnp.float32),
                [v.pool for v in views])

    ids = np.zeros((1, bucket), np.int32)
    ids[0, :p] = tokens[:p]
    row, pools = eng._run_eval(
        jax.jit(prefill, donate_argnums=(1,)), eng._state_vals, cache.pools,
        jnp.asarray(ids), jnp.asarray(cache.block_tables[slot]),
        jnp.asarray(p, jnp.int32))
    rows = [row]
    step = jax.jit(decode, donate_argnums=(1,))
    for i in range(steps):
        # fresh host arrays a step: jnp.asarray may alias them, and the
        # step runs after this loop has moved on
        lens = np.zeros((eng.max_slots,), np.int32)
        lens[slot] = p + i
        toks = np.zeros((eng.max_slots,), np.int32)
        toks[slot] = tokens[p + i]
        out, pools = eng._run_eval(
            step, eng._state_vals, pools, jnp.asarray(toks),
            jnp.asarray(cache.block_tables), jnp.asarray(lens))
        rows.append(out[slot])
    cache.pools = pools
    out = np.asarray(jnp.stack(rows))
    cache.release_slot(slot)
    return out, bucket


def program_routing(model, tokens):
    """[chosen experts [T, k] an expert layer] of the program's plain
    forward over ``tokens``: each expert layer's input, caught on its
    way in, through the layer's own router (group-limited where the
    layer says so)."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.parallel.moe import route

    caught = []
    # an expert block is whatever holds the layer's ``experts``: a
    # layer's second half (``mlp``) or a layer's one mixer
    sparse = [block for block in model.sublayers()
              if hasattr(block, "experts")]
    hooks = [mlp.register_forward_pre_hook(
        lambda _layer, inputs: caught.append(inputs[0]))
        for mlp in sparse]
    try:
        model(paddle.to_tensor(np.asarray([tokens], np.int32)))
    finally:
        for hook in hooks:
            hook.remove()
    out = []
    for mlp, x in zip(sparse, caught):
        e = mlp.experts
        # the scaling factor changes no choice; a selection bias does
        bias = getattr(mlp, "e_score_correction_bias", None)
        _, chosen, _ = route(x.reshape(-1, x.shape[-1]),
                             e.gate_weight._value, e.top_k,
                             e.norm_topk_prob, getattr(mlp, "n_group", 1),
                             getattr(mlp, "topk_group", 1),
                             select_bias=None if bias is None
                             else bias._value)
        out.append(np.asarray(chosen))
    return out


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--config", required=True)
    parser.add_argument("--prompts", default="512,384")
    parser.add_argument("--steps", type=int, default=8)
    parser.add_argument("--seed", type=int, default=3000000019)
    args = parser.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    sys.path.insert(0, ROOT)

    import jax
    import numpy as np

    import run as bench
    from paddle_tpu import serving
    from paddle_tpu.core import compile_cache

    compile_cache.configure()
    manifest = bench.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = bench.find(manifest["configs"], args.config, "config")
    cfg = bench.load_json(os.path.join(ROOT, entry["file"]))
    family = bench.load_module("families", cfg["family"])
    model = family.build_model(cfg, args.seed, training=False)
    weights = family.weights_of(model)
    prompts = [int(n) for n in args.prompts.split(",")]
    eng = serving.Engine(model, max_slots=4, num_blocks=512, block_size=16,
                         max_model_len=2 * max(prompts))
    rng = np.random.default_rng(args.seed)
    device = jax.devices()[0]
    result = {"config": args.config, "seed": args.seed,
              "device": {"platform": device.platform,
                         "kind": device.device_kind}, "prompts": []}
    for n in prompts:
        tokens = rng.integers(0, cfg["vocab_size"], n + args.steps).tolist()
        got, bucket = logits_through_cache(eng, tokens, args.steps)
        reference = getattr(family, "reference_forward", None)
        if reference is None:
            want, ref_routing = family.reference_logits(weights, cfg,
                                                        tokens), None
        else:
            want, ref_routing = reference(weights, cfg, tokens)
        want = np.asarray(want)[n - 1:]
        largest = float(np.abs(want).max())
        diff = np.abs(got - want).max(axis=-1)
        row = {"prompt": n, "bucket": bucket, "steps": args.steps,
               "max_abs_logit": largest,
               "max_abs_diff_prefill_row": float(diff[0]),
               "max_abs_diff_decode_rows": float(diff[1:].max()),
               "max_abs_diff_over_max_abs_logit": float(diff.max()) / largest,
               "argmax_agree": int((got.argmax(-1)
                                    == want.argmax(-1)).sum()),
               "rows": int(len(diff))}
        if ref_routing is not None:
            flips = total = 0
            for mine, theirs in zip(program_routing(model, tokens),
                                    ref_routing):
                theirs = np.asarray(theirs)
                for a, b in zip(mine, theirs):
                    flips += len(set(a.tolist()) - set(b.tolist()))
                total += theirs.size
            row["topk_selections"] = total
            row["topk_selections_that_differ"] = flips
        result["prompts"].append(row)
        print("[parity] " + json.dumps(row), flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
