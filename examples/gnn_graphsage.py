"""GNN mini-batch training against the PS graph table.

The GraphSAGE pattern over the distributed graph service (reference
ps/table/common_graph_table.h + pscore graph ops): the server owns the
graph (adjacency + node features) and answers fixed-shape sampling
queries, so the device only ever compiles over dense [batch, k, dim]
tensors — no ragged structure reaches XLA. Two-hop neighborhood:
sample -> gather -> mean-aggregate -> concat -> dense layers.

    JAX_PLATFORMS=cpu python examples/gnn_graphsage.py
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.nn as nn  # noqa: E402
import paddle_tpu.nn.functional as F  # noqa: E402
from paddle_tpu.distributed.ps import PsClient, PsServer  # noqa: E402


def build_two_community_graph(cli, n=200, dim=16, seed=0):
    """Two communities with dense intra-links and sparse cross-links;
    features carry a noisy community signal — the classic setting where
    neighbor aggregation beats a featurewise classifier."""
    rng = np.random.RandomState(seed)
    cli.create_graph_table(0, feat_dim=dim, seed=seed)
    labels = (np.arange(n) >= n // 2).astype(np.int32)
    src, dst = [], []
    for u in range(n):
        same = np.where(labels == labels[u])[0]
        other = np.where(labels != labels[u])[0]
        nbrs = np.concatenate([rng.choice(same, 8),
                               rng.choice(other, 1)])
        src += [u] * len(nbrs)
        dst += list(nbrs)
    cli.graph_add_edges(0, src, dst)
    feats = rng.randn(n, dim).astype(np.float32) * 1.0
    feats[:, 0] += (labels * 2 - 1) * 0.5  # weak signal, needs hops
    cli.graph_set_node_feat(0, np.arange(n), feats)
    return labels


class SageNet(nn.Layer):
    def __init__(self, dim, hidden=32):
        super().__init__()
        self.l1 = nn.Linear(2 * dim, hidden)
        self.l2 = nn.Linear(hidden, 2)

    def forward(self, self_f, agg_f):
        h = paddle.concat([self_f, agg_f], axis=-1)
        return self.l2(F.relu(self.l1(h)))


def sample_batch(cli, labels, batch_size=64, k=8, dim=16):
    ids = cli.graph_random_nodes(0, batch_size)
    nb = cli.graph_sample_neighbors(0, ids, k)
    valid = nb >= 0
    nf = cli.graph_get_node_feat(
        0, np.where(valid, nb, 0).reshape(-1)).reshape(
            batch_size, k, dim)
    mask = valid[..., None].astype(np.float32)
    agg = (nf * mask).sum(1) / np.maximum(mask.sum(1), 1.0)
    self_f = cli.graph_get_node_feat(0, ids)
    return (paddle.to_tensor(self_f), paddle.to_tensor(agg),
            paddle.to_tensor(labels[ids]))


def main():
    dim = 16
    srv = PsServer()
    try:
        with PsClient(port=srv.port) as cli:
            labels = build_two_community_graph(cli, dim=dim)
            paddle.seed(0)
            net = SageNet(dim)
            opt = paddle.optimizer.Adam(learning_rate=0.01,
                                        parameters=net.parameters())
            for step in range(60):
                self_f, agg, y = sample_batch(cli, labels, dim=dim)
                loss = F.cross_entropy(net(self_f, agg), y)
                loss.backward()
                opt.step()
                opt.clear_grad()
                if step % 20 == 0:
                    print("step %3d loss %.4f" % (step, float(loss)))
            # evaluate on every node
            ids = np.arange(len(labels))
            nb = cli.graph_sample_neighbors(0, ids, 8)
            valid = nb >= 0
            nf = cli.graph_get_node_feat(
                0, np.where(valid, nb, 0).reshape(-1)).reshape(
                    len(ids), 8, dim)
            m = valid[..., None].astype(np.float32)
            agg = (nf * m).sum(1) / np.maximum(m.sum(1), 1.0)
            logits = net(paddle.to_tensor(cli.graph_get_node_feat(0, ids)),
                         paddle.to_tensor(agg))
            pred = np.asarray(logits.numpy()).argmax(-1)
            acc = float((pred == labels).mean())
            print("full-graph accuracy: %.3f" % acc)
            assert acc > 0.8, acc
    finally:
        srv.stop()


if __name__ == "__main__":
    main()
