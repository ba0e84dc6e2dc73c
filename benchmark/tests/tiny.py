"""Tiny sizes for the CPU tests: a configuration of the mistral family
and the two mixes, shrunk. The cells' real files are never edited."""
import json
import os

import run as bench

CONFIG = dict(family="mistral", hidden_size=64, intermediate_size=128,
              num_attention_heads=4, num_key_value_heads=2, head_dim=16,
              vocab_size=256, num_hidden_layers=2,
              max_position_embeddings=256, rms_norm_eps=1e-5,
              rope_theta=1e6, tie_word_embeddings=False,
              torch_dtype="float32", recompute=True)
BACKLOG = dict(
    engine=dict(max_slots=4, num_blocks=64, block_size=8,
                max_model_len=128),
    pool=8, pair_seed=1,
    prompt_tokens=dict(dist="lognormal", median=24, sigma=0.6, min=8,
                       max=64),
    output_tokens=dict(dist="lognormal", median=12, sigma=0.5, min=6,
                       max=24),
    queue_depth=8, reference_prompts=[6, 20], reference_tokens=4,
    trace_seconds=1)
TRAIN = dict(batch=2, seq_len=32, trace_steps=2)
SEED = 3000000019       # more than 32 signed bits hold


def mix(name, **changes):
    """The real mix's file with the tiny sizes laid over it."""
    path = os.path.join(bench.HERE, "traffic", name + ".json")
    with open(path) as f:
        return dict(json.load(f), **changes)


def quiet(_msg):
    pass
