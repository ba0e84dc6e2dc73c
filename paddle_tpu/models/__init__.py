"""Flagship model families (the reference ecosystem's ERNIE/GPT configs live
in PaddleNLP; the framework repo carries the layers. We ship the model zoo
in-tree so the distributed configs are testable)."""
from .ernie import (  # noqa: F401
    ErnieConfig,
    ErnieForPretraining,
    ErnieForSequenceClassification,
    ErnieModel,
)
from .gpt import GPTModel  # noqa: F401
from .llama import LlamaConfig, LlamaForCausalLM, LlamaModel  # noqa: F401
from .qwen3_next import (  # noqa: F401
    Qwen3NextConfig,
    Qwen3NextForCausalLM,
)
