"""Model FLOP/s utilisation: model FLOPs a token (forward + backward,
causal attention, recompute not counted; the family's own function of
the shapes) x tokens/s/chip over the chip's bf16 peak."""


def read(obs):
    rate = obs.get("end_to_end", {}).get("train_tok_s_chip")
    if rate is None:
        return None
    per_token = obs["family"].train_flops_per_token(obs["config"],
                                                    obs["seq_len"])
    obs["log"]("train.mfu: %.4f GFLOP a token" % (per_token / 1e9))
    return 100.0 * per_token * rate / obs["peaks"]["flops_bf16"]
