"""Rows a prefill runs past the self-decoder (the full-attention layer's
queries, the cross-decoder and the head), a mean over the engine's
prefills: 1.00 while the YOCO skip holds, the prompt's bucket if it were
lost. From ``Engine.stats()["yoco"]["cross_rows_per_prefill"]``; nothing
on a program that keeps no such account (the parent)."""


def read(obs):
    yoco = obs.get("counters", {}).get("yoco")
    if not yoco:
        return None
    return yoco["cross_rows_per_prefill"]
