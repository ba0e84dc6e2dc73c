"""Tokens the latent cache held for the decoding slots, a mean over the
engine's recent decode steps: what a decode step's latent-attention
kernel reads, a row a token a layer. From
``Engine.stats()["latent"]["cached_tokens"]``; nothing on a program
without a latent cache (the parent)."""


def read(obs):
    latent = obs.get("counters", {}).get("latent")
    if not latent:
        return None
    return latent["cached_tokens"]
