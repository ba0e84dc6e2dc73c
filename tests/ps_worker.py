"""Wide&deep PS worker (reference dist_fleet_ctr.py pattern): pulls real
embedding rows from the network PS, computes forward/backward on device
(jax), pushes sparse grads back; dense layers train locally.

Env: PADDLE_PSERVER=host:port, PS_WORKER_ID, PS_NUM_STEPS.
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np


def synth_batch(rng, batch, n_feat, vocab, teacher):
    ids = rng.randint(0, vocab, (batch, n_feat)).astype(np.int64)
    # teacher: fixed per-id scores; label = sign of their sum — directly
    # learnable by the wide (per-id scalar) table
    y = (teacher[ids].sum(1) > 0).astype(np.float32)
    return ids, y


def main():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.distributed.ps.service import PsClient

    host, _, port = os.environ["PADDLE_PSERVER"].partition(":")
    wid = int(os.environ["PADDLE_TRAINER_ID"])
    steps = int(os.environ.get("PS_NUM_STEPS", "30"))
    dim, n_feat, vocab, batch = 8, 4, 100, 32

    cli = PsClient(host, int(port))
    # table 0: deep embeddings (adam), table 1: wide scalar weights (sgd)
    # (tables are created by the test driver before workers start)
    cli._dims[0] = dim
    cli._dims[1] = 1

    # all workers share the same teacher (fixed seed), each sees its own
    # data stream
    teacher = np.random.RandomState(7).choice(
        [-1.0, 1.0], size=vocab).astype(np.float32)
    rng = np.random.RandomState(100 + wid)
    w1 = rng.randn(n_feat * dim, 16).astype(np.float32) * 0.3
    b1 = np.zeros(16, np.float32)
    w2 = rng.randn(16, 1).astype(np.float32) * 0.3
    b2 = np.zeros(1, np.float32)

    def fwd(emb, wide, params, y):
        w1, b1, w2, b2 = params
        h = jnp.tanh(emb.reshape(emb.shape[0], -1) @ w1 + b1)
        logit = (h @ w2 + b2)[:, 0] + wide.sum(axis=1)
        # stable BCE with logits
        loss = jnp.maximum(logit, 0) - logit * y + jnp.log1p(
            jnp.exp(-jnp.abs(logit)))
        return loss.mean()

    grad_fn = jax.jit(jax.grad(fwd, argnums=(0, 1, 2)))
    loss_fn = jax.jit(fwd)

    lr = 0.1
    losses = []
    for step in range(steps):
        ids, y = synth_batch(rng, batch, n_feat, vocab, teacher)
        flat = ids.reshape(-1)
        emb = cli.pull_sparse(0, flat, dim).reshape(batch, n_feat, dim)
        wide = cli.pull_sparse(1, flat, 1).reshape(batch, n_feat)
        params = (w1, b1, w2, b2)
        losses.append(float(loss_fn(emb, wide, params, y)))
        g_emb, g_wide, g_params = grad_fn(emb, wide, params, y)
        # push REAL gradients; server-side accessors apply the rules
        cli.push_sparse(0, flat, np.asarray(g_emb).reshape(-1, dim))
        cli.push_sparse(1, flat, np.asarray(g_wide).reshape(-1, 1))
        w1 -= lr * np.asarray(g_params[0])
        b1 -= lr * np.asarray(g_params[1])
        w2 -= lr * np.asarray(g_params[2])
        b2 -= lr * np.asarray(g_params[3])
    cli.close()
    print("PS_RESULT " + json.dumps({"worker": wid, "losses": losses}))
    sys.stdout.flush()


if __name__ == "__main__":
    main()
