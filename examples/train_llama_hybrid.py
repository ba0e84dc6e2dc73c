"""Hybrid-parallel Llama training: dp x mp x ZeRO in ONE compiled step.

The flagship distributed config (BASELINE.md "GPT/Llama TP+PP hybrid"):
every parallelism dimension enters as a sharding; XLA inserts and
overlaps the collectives. Run on 8 virtual CPU devices:

    JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/train_llama_hybrid.py

or unchanged on a real TPU slice (the mesh maps onto ICI).
"""
import numpy as np

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.distributed import mesh
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.parallel.engine import CompiledTrainStep


def main(steps=10):
    import jax

    n = len(jax.devices())
    dp, mp, sharding = (2, 2, 2) if n >= 8 else (1, 1, 1)
    mesh.build_hybrid_mesh(dp=dp, mp=mp, sharding=sharding,
                           devices=jax.devices()[:dp * mp * sharding])
    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=512, hidden_size=128,
                      intermediate_size=256, num_hidden_layers=2,
                      num_attention_heads=4, max_position_embeddings=256,
                      use_parallel=True)
    model = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=3e-4,
                                 parameters=model.parameters())

    def loss_fn(logits, labels):
        return F.cross_entropy(logits.reshape([-1, cfg.vocab_size]),
                               labels.reshape([-1]))

    # zero_stage=2: grads reduce-scattered + opt state sharded over
    # 'sharding'; stage 3 would shard the params themselves
    step = CompiledTrainStep(model, loss_fn, opt, zero_stage=2)
    rng = np.random.RandomState(0)
    batch, seq = 4 * dp * sharding, 64
    for i in range(steps):
        ids = paddle.to_tensor(
            rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32))
        labels = paddle.to_tensor(
            rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32))
        loss = step(ids, labels)
        print("step %d loss %.4f" % (i, float(loss)))
    # prove the q_proj weight is tensor-parallel sharded
    q = dict(model.named_parameters())[
        "llama.layers.0.self_attn.q_proj.weight"]
    print("q_proj sharding:", q._value.sharding.spec)
    return float(loss)


if __name__ == "__main__":
    main()
