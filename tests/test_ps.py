"""Parameter-server tests: native C++ table service (csrc/ps.cc) over
real TCP, accessor rules vs numpy oracles, geo-async mode, save/load,
and a wide&deep e2e run with separate worker PROCESSES pulling/pushing
real embeddings (reference test pattern: unittests/ps/,
test_dist_fleet_ctr.py spawning local brpc server+workers).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from paddle_tpu.distributed.ps import (
    GeoWorkerCache,
    PsClient,
    PsServer,
    TheOnePSRuntime,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def server():
    srv = PsServer()
    yield srv
    srv.stop()


class TestAccessorRules:
    def test_sgd(self, server):
        with PsClient(port=server.port) as cli:
            cli.create_sparse_table(0, 3, optimizer="sgd", lr=0.5,
                                    init_std=0.0)
            g = np.array([[1.0, 2.0, 3.0]], np.float32)
            cli.push_sparse(0, [7], g)
            np.testing.assert_allclose(cli.pull_sparse(0, [7]), -0.5 * g)

    def test_adagrad(self, server):
        with PsClient(port=server.port) as cli:
            cli.create_sparse_table(0, 2, optimizer="adagrad", lr=0.1,
                                    init_std=0.0)
            g = np.array([[2.0, 4.0]], np.float32)
            cli.push_sparse(0, [1], g)
            want = -0.1 * g / (np.abs(g) + 1e-8)
            np.testing.assert_allclose(cli.pull_sparse(0, [1]), want,
                                       rtol=1e-5)

    def test_adam(self, server):
        with PsClient(port=server.port) as cli:
            cli.create_sparse_table(0, 2, optimizer="adam", lr=0.01,
                                    init_std=0.0)
            g = np.array([[3.0, -2.0]], np.float32)
            cli.push_sparse(0, [4], g)
            # first adam step with zero init: w = -lr * sign(g)
            np.testing.assert_allclose(
                cli.pull_sparse(0, [4]), -0.01 * np.sign(g), rtol=1e-4)

    def test_dense_table(self, server):
        with PsClient(port=server.port) as cli:
            cli.create_dense_table(2, 4, optimizer="sgd", lr=1.0)
            cli.push_dense(2, np.arange(4, dtype=np.float32))
            np.testing.assert_allclose(cli.pull_dense(2, 4),
                                       -np.arange(4, dtype=np.float32))

    def test_create_on_miss_uses_init_std(self, server):
        with PsClient(port=server.port) as cli:
            cli.create_sparse_table(0, 16, optimizer="sgd", lr=0.1,
                                    init_std=0.05, seed=3)
            rows = cli.pull_sparse(0, list(range(200)))
            assert 0.02 < rows.std() < 0.08
            # same rows on re-pull (created once)
            again = cli.pull_sparse(0, list(range(200)))
            np.testing.assert_allclose(rows, again)
            assert cli.sparse_size(0) == 200

    def test_save_load_roundtrip(self, server, tmp_path):
        with PsClient(port=server.port) as cli:
            cli.create_sparse_table(0, 4, init_std=0.1, seed=9)
            rows = cli.pull_sparse(0, [1, 2, 3])
            path = str(tmp_path / "table0.bin")
            cli.save(0, path)
            cli.create_sparse_table(5, 4, init_std=0.0)
            cli.load(5, path)
            np.testing.assert_allclose(cli.pull_sparse(5, [1, 2, 3], 4),
                                       rows)


class TestGeoMode:
    def test_two_geo_workers_merge_deltas(self, server):
        with PsClient(port=server.port) as c0, \
                PsClient(port=server.port) as c1:
            c0.create_sparse_table(0, 2, optimizer="sgd", lr=1.0,
                                   init_std=0.0)
            g0 = GeoWorkerCache(c0, 0, 2, push_every=1000)
            g1 = GeoWorkerCache(c1, 0, 2, push_every=1000)
            g0.pull([1])
            g1.pull([1])
            g0.apply_local([1], np.array([[1.0, 0.0]]), lr=1.0)
            g1.apply_local([1], np.array([[0.0, 2.0]]), lr=1.0)
            g0.sync()
            g1.sync()
            # server merged both deltas additively (geo-SGD)
            np.testing.assert_allclose(c0.pull_sparse(0, [1]),
                                       [[-1.0, -2.0]])
            # after sync, both caches see the merged row
            g0.sync()
            np.testing.assert_allclose(g0.pull([1]), [[-1.0, -2.0]])


class TestCtrAccessor:
    """Reference ctr_accessor.cc semantics: show/click stats, chained
    SGD rules for embed/embedx, decay + threshold shrink."""

    def test_show_click_accumulate_and_naive_rule(self, server):
        with PsClient(port=server.port) as cli:
            cli.create_ctr_table(0, dim=4, rule="sgd", lr=0.5,
                                 init_range=0.0)
            gx = np.full((1, 4), 2.0, np.float32)
            cli.push_ctr(0, [7], shows=[1.0], clicks=[1.0],
                         embed_g=[3.0], embedx_g=gx)
            shows, clicks, w, wx = cli.pull_ctr(0, [7])
            np.testing.assert_allclose(shows, [1.0])
            np.testing.assert_allclose(clicks, [1.0])
            # naive rule: w -= lr * g (init 0)
            np.testing.assert_allclose(w, [-1.5])
            np.testing.assert_allclose(wx, -0.5 * gx)
            # second push accumulates stats
            cli.push_ctr(0, [7], shows=[2.0], clicks=[0.0],
                         embed_g=[0.0], embedx_g=np.zeros((1, 4)))
            shows, clicks, _, _ = cli.pull_ctr(0, [7])
            np.testing.assert_allclose(shows, [3.0])
            np.testing.assert_allclose(clicks, [1.0])

    def test_adagrad_rule_oracle(self, server):
        with PsClient(port=server.port) as cli:
            lr, g2 = 0.1, 3.0
            cli.create_ctr_table(0, dim=2, rule="adagrad", lr=lr,
                                 init_range=0.0, initial_g2sum=g2)
            gx = np.array([[2.0, 4.0]], np.float32)
            # push_show=1 -> scale 1; first step g2sum starts at 0:
            # w -= lr * g * sqrt(g2 / (g2 + 0))
            cli.push_ctr(0, [1], shows=[1.0], clicks=[0.0],
                         embed_g=[1.0], embedx_g=gx)
            _, _, w, wx = cli.pull_ctr(0, [1])
            np.testing.assert_allclose(wx, -lr * gx, rtol=1e-5)
            np.testing.assert_allclose(w, [-lr], rtol=1e-5)
            # second step: g2sum = mean(g^2) from step 1
            cli.push_ctr(0, [1], shows=[1.0], clicks=[0.0],
                         embed_g=[1.0], embedx_g=gx)
            g2sum = float((gx ** 2).mean())
            want = -lr * gx - lr * gx * np.sqrt(g2 / (g2 + g2sum))
            _, _, _, wx2 = cli.pull_ctr(0, [1])
            np.testing.assert_allclose(wx2, want, rtol=1e-5)

    def test_show_scale_divides_gradient(self, server):
        with PsClient(port=server.port) as cli:
            cli.create_ctr_table(0, dim=2, rule="adagrad", lr=0.1,
                                 init_range=0.0, initial_g2sum=3.0)
            # push_show=4 -> grads scaled by 1/4 (reference show_scale)
            gx = np.array([[4.0, 8.0]], np.float32)
            cli.push_ctr(0, [2], shows=[4.0], clicks=[0.0],
                         embed_g=[0.0], embedx_g=gx)
            _, _, _, wx = cli.pull_ctr(0, [2])
            np.testing.assert_allclose(wx, -0.1 * gx / 4.0, rtol=1e-5)

    def test_adam_rule_ignores_show_scale(self, server):
        """Reference sparse_sgd_rule.cc parity: only the adagrad rules
        divide the gradient by show; SparseAdamSGDRule consumes it raw.
        Adam's m/sqrt(v) is scale-invariant except through eps, so probe
        with a gradient small enough that eps dominates: raw g=1e-7 gives
        step ~ lr*g/(g+eps) = 0.909*lr, while a /show=4 version would
        give lr*(g/4)/((g/4)+eps) = 0.714*lr."""
        with PsClient(port=server.port) as cli:
            cli.create_ctr_table(0, dim=2, rule="adam", lr=0.01,
                                 init_range=0.0)
            g = np.float32(1e-7)
            gx = np.full((1, 2), g, np.float32)
            cli.push_ctr(0, [3], shows=[4.0], clicks=[0.0],
                         embed_g=[0.0], embedx_g=gx)
            _, _, _, wx = cli.pull_ctr(0, [3])
            want = -0.01 * g / (g + 1e-8)
            np.testing.assert_allclose(wx, np.full((1, 2), want), rtol=1e-3)

    def test_shrink_decay_and_delete(self, server):
        with PsClient(port=server.port) as cli:
            cli.create_ctr_table(0, dim=2, rule="sgd", lr=0.1,
                                 init_range=0.0, nonclk_coeff=0.1,
                                 click_coeff=1.0, decay_rate=0.5,
                                 delete_threshold=0.8)
            z = np.zeros((1, 2), np.float32)
            # hot row: score after decay = (10-5)*0.5*0.1 + 5*0.5*1 = 2.75
            cli.push_ctr(0, [1], shows=[10.0], clicks=[5.0],
                         embed_g=[0.0], embedx_g=z)
            # cold row: score after decay = 1*0.5*0.1 = 0.05 < 0.8
            cli.push_ctr(0, [2], shows=[1.0], clicks=[0.0],
                         embed_g=[0.0], embedx_g=z)
            assert cli.ctr_shrink(0) == 1
            assert cli.sparse_size(0) == 1
            shows, clicks, _, _ = cli.pull_ctr(0, [1])
            np.testing.assert_allclose(shows, [5.0])   # decayed
            np.testing.assert_allclose(clicks, [2.5])

    def test_unseen_days_eviction(self, server):
        with PsClient(port=server.port) as cli:
            cli.create_ctr_table(0, dim=2, rule="sgd",
                                 decay_rate=1.0, delete_threshold=0.0,
                                 delete_after_unseen_days=2.0)
            cli.push_ctr(0, [1], shows=[100.0], clicks=[100.0],
                         embed_g=[0.0], embedx_g=np.zeros((1, 2)))
            assert cli.ctr_shrink(0) == 0  # unseen=1
            assert cli.ctr_shrink(0) == 0  # unseen=2
            assert cli.ctr_shrink(0) == 1  # unseen=3 > 2 -> deleted
            assert cli.sparse_size(0) == 0


class TestSsdSpillTable:
    """Reference ssd_sparse_table.cc: bounded memory + disk overflow."""

    def test_lru_spill_and_readback(self, server, tmp_path):
        with PsClient(port=server.port) as cli:
            cli.create_sparse_table(0, 2, optimizer="sgd", lr=1.0,
                                    init_std=0.0)
            cli.set_spill(0, mem_capacity=4,
                          path=str(tmp_path / "spill.bin"))
            # write 10 distinct rows via pushes (create-on-miss)
            for i in range(10):
                cli.push_sparse(0, [i], np.full((1, 2), float(i + 1),
                                                np.float32))
            assert cli.sparse_size(0) == 10      # total incl. spilled
            assert cli.mem_rows(0) <= 4          # memory bounded
            # spilled rows read back intact (w = -g after lr=1 sgd)
            for i in range(10):
                np.testing.assert_allclose(
                    cli.pull_sparse(0, [i]), [[-(i + 1.0), -(i + 1.0)]])
            # pulls promoted rows through memory without exceeding cap
            assert cli.mem_rows(0) <= 4

    def test_set_spill_on_populated_table(self, server, tmp_path):
        # regression: enabling spill on a table that already holds rows
        # must enter them into the LRU (else the new row could be its
        # own eviction victim -> server use-after-free) and enforce the
        # capacity on the pre-existing rows too
        with PsClient(port=server.port) as cli:
            cli.create_sparse_table(0, 2, optimizer="sgd", lr=1.0,
                                    init_std=0.0)
            for i in range(8):
                cli.push_sparse(0, [i], np.full((1, 2), float(i + 1),
                                                np.float32))
            cli.set_spill(0, mem_capacity=3,
                          path=str(tmp_path / "spill.bin"))
            assert cli.mem_rows(0) <= 3  # pre-existing rows evicted
            # new row insert right after set_spill (the crash scenario)
            cli.push_sparse(0, [100], np.full((1, 2), 0.5, np.float32))
            np.testing.assert_allclose(cli.pull_sparse(0, [100]),
                                       [[-0.5, -0.5]])
            assert cli.sparse_size(0) == 9
            for i in range(8):
                np.testing.assert_allclose(
                    cli.pull_sparse(0, [i]), [[-(i + 1.0), -(i + 1.0)]])

    def test_spilled_rows_survive_save_load(self, server, tmp_path):
        with PsClient(port=server.port) as cli:
            cli.create_sparse_table(0, 2, optimizer="sgd", lr=1.0,
                                    init_std=0.0)
            cli.set_spill(0, mem_capacity=2,
                          path=str(tmp_path / "spill.bin"))
            for i in range(6):
                cli.push_sparse(0, [i], np.full((1, 2), float(i + 1),
                                                np.float32))
            cli.save(0, str(tmp_path / "table.bin"))
            # fresh table (same layout), load -> all 6 rows back
            cli.create_sparse_table(1, 2, optimizer="sgd", lr=1.0,
                                    init_std=0.0)
            cli.load(1, str(tmp_path / "table.bin"))
            assert cli.sparse_size(1) == 6
            for i in range(6):
                np.testing.assert_allclose(
                    cli.pull_sparse(1, [i]), [[-(i + 1.0), -(i + 1.0)]])


class TestCommunicator:
    """Reference AsyncCommunicator: client-side merge + batched flush."""

    def test_async_merge_by_id(self, server):
        from paddle_tpu.distributed.ps import Communicator

        with PsClient(port=server.port) as cli:
            cli.create_sparse_table(0, 2, optimizer="sgd", lr=1.0,
                                    init_std=0.0)
            comm = Communicator(port=server.port, mode="async",
                                merge_threshold=1000,
                                flush_interval_ms=10_000)
            try:
                # same id pushed 3x -> merged client-side into ONE
                # gradient before the server applies sgd once
                for _ in range(3):
                    comm.push_sparse(0, [5], np.ones((1, 2), np.float32),
                                     dim=2)
                comm.push_sparse(0, [6], np.full((1, 2), 2.0, np.float32),
                                 dim=2)
                comm.flush()
                np.testing.assert_allclose(cli.pull_sparse(0, [5]),
                                           [[-3.0, -3.0]])
                np.testing.assert_allclose(cli.pull_sparse(0, [6]),
                                           [[-2.0, -2.0]])
                assert comm.flushed_batches() >= 1
            finally:
                comm.stop()

    def test_background_flush_by_threshold(self, server):
        import time

        from paddle_tpu.distributed.ps import Communicator

        with PsClient(port=server.port) as cli:
            cli.create_dense_table(1, 4, optimizer="sgd", lr=1.0)
            comm = Communicator(port=server.port, mode="async",
                                merge_threshold=2, flush_interval_ms=20)
            try:
                comm.push_dense(1, np.ones(4, np.float32))
                comm.push_dense(1, np.ones(4, np.float32))
                deadline = time.time() + 5.0
                while time.time() < deadline:
                    if np.allclose(cli.pull_dense(1, 4), -2.0):
                        break
                    time.sleep(0.05)
                np.testing.assert_allclose(cli.pull_dense(1, 4), -2.0)
            finally:
                comm.stop()

    def test_geo_mode_merges_deltas(self, server):
        from paddle_tpu.distributed.ps import Communicator

        with PsClient(port=server.port) as cli:
            cli.create_sparse_table(0, 2, optimizer="sgd", lr=1.0,
                                    init_std=0.0)
            comm = Communicator(port=server.port, mode="geo",
                                merge_threshold=1000,
                                flush_interval_ms=10_000)
            try:
                comm.push_sparse(0, [3], np.array([[0.5, -0.5]]), dim=2)
                comm.flush()
                # geo: delta ADDED to weights (no optimizer rule)
                np.testing.assert_allclose(cli.pull_sparse(0, [3]),
                                           [[0.5, -0.5]])
            finally:
                comm.stop()


class TestGraphTable:
    """Reference common_graph_table.h: server-side graph + sampling."""

    def _build(self, cli):
        cli.create_graph_table(0, feat_dim=4, seed=0)
        # star: 0 -> 1..5; chain: 1 -> 2
        cli.graph_add_edges(0, [0] * 5 + [1], [1, 2, 3, 4, 5, 2])
        ids = np.arange(6)
        cli.graph_set_node_feat(0, ids,
                                np.eye(6, 4, dtype=np.float32) + 1.0)

    def test_sample_neighbors_within_adjacency(self, server):
        with PsClient(port=server.port) as cli:
            self._build(cli)
            nb = cli.graph_sample_neighbors(0, [0, 1, 5], 3)
            assert nb.shape == (3, 3)
            assert set(nb[0]) <= {1, 2, 3, 4, 5}      # sampled from 0's
            assert len(set(nb[0])) == 3               # w/o replacement
            assert list(nb[1]) == [2, -1, -1]         # degree 1, padded
            assert list(nb[2]) == [-1, -1, -1]        # no out-edges

    def test_degree_and_features_roundtrip(self, server):
        with PsClient(port=server.port) as cli:
            self._build(cli)
            np.testing.assert_array_equal(
                cli.graph_node_degree(0, [0, 1, 5]), [5, 1, 0])
            f = cli.graph_get_node_feat(0, [2, 0])
            np.testing.assert_allclose(
                f, (np.eye(6, 4, dtype=np.float32) + 1.0)[[2, 0]])
            # unknown node -> zero features (create-on-miss is wrong for
            # graphs; absence must be visible)
            np.testing.assert_allclose(
                cli.graph_get_node_feat(0, [99]), 0.0)

    def test_random_nodes_cover_node_set(self, server):
        with PsClient(port=server.port) as cli:
            self._build(cli)
            ids = cli.graph_random_nodes(0, 64)
            assert set(ids) <= set(range(6))
            assert len(set(ids)) > 1  # actually random, not constant

    def test_graphsage_style_aggregation_step(self, server):
        """e2e: sample -> gather feats -> mean-aggregate on device (the
        GNN mini-batch pattern the reference serves via pscore ops)."""
        import jax.numpy as jnp

        with PsClient(port=server.port) as cli:
            self._build(cli)
            batch = cli.graph_random_nodes(0, 8)
            nb = cli.graph_sample_neighbors(0, batch, 4)
            valid = nb >= 0
            feats = cli.graph_get_node_feat(
                0, np.where(valid, nb, 0).reshape(-1)).reshape(8, 4, 4)
            self_f = cli.graph_get_node_feat(0, batch)
            mask = jnp.asarray(valid, jnp.float32)[..., None]
            agg = (jnp.asarray(feats) * mask).sum(1) / jnp.maximum(
                mask.sum(1), 1.0)
            h = jnp.concatenate([jnp.asarray(self_f), agg], axis=-1)
            assert h.shape == (8, 8) and bool(jnp.isfinite(h).all())


class TestRuntimeFacade:
    def test_remote_runtime(self):
        rt = TheOnePSRuntime()
        rt.init_server()
        rt.init_worker()
        assert rt.is_remote
        rt.create_sparse_table("emb", 4, optimizer="sgd", lr=0.5,
                               init_std=0.0)
        rt.push_sparse("emb", [3], np.ones((1, 4), np.float32))
        np.testing.assert_allclose(rt.pull_sparse("emb", [3]), -0.5)
        rt.create_dense_table("fc", (2, 2), lr=1.0)
        rt.push_dense("fc", np.ones((2, 2), np.float32))
        np.testing.assert_allclose(rt.pull_dense("fc"), -1.0)
        rt.stop()


class TestWideDeepE2E:
    def test_two_worker_processes_train(self):
        """Real network e2e: server in this process (C++ threads), two
        separate WORKER PROCESSES pull/push embeddings; loss drops and
        the table materializes rows."""
        srv = PsServer()
        boot = PsClient(port=srv.port)
        boot.create_sparse_table(0, 8, optimizer="adam", lr=0.02)
        boot.create_sparse_table(1, 1, optimizer="sgd", lr=0.1)
        procs = []
        try:
            for wid in range(2):
                env = dict(os.environ)
                env.update({
                    "PYTHONPATH": REPO + os.pathsep
                    + env.get("PYTHONPATH", ""),
                    "JAX_PLATFORMS": "cpu",
                    "PADDLE_PSERVER": "127.0.0.1:%d" % srv.port,
                    "PS_WORKER_ID": str(wid),
                    "PS_NUM_STEPS": "40",
                })
                procs.append(subprocess.Popen(
                    [sys.executable, os.path.join(REPO, "tests",
                                                  "ps_worker.py")],
                    env=env, stdout=subprocess.PIPE,
                    stderr=subprocess.PIPE, text=True))
            results = {}
            for p in procs:
                out, err = p.communicate(timeout=300)
                assert p.returncode == 0, (out[-1500:], err[-2500:])
                line = [l for l in out.splitlines()
                        if l.startswith("PS_RESULT ")][0]
                rec = json.loads(line[len("PS_RESULT "):])
                results[rec["worker"]] = rec["losses"]
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
        for wid, losses in results.items():
            first = np.mean(losses[:5])
            last = np.mean(losses[-5:])
            assert last < first - 0.05, (wid, first, last)
        # embeddings really materialized on the server
        assert boot.sparse_size(0) > 50
        boot.close()
        srv.stop()


class TestPsSaturationTool:
    def test_components_and_scaling_run(self, tmp_path):
        """tools/ps_saturation.py (VERDICT r4 weak #6): the PS-path
        binding/scaling study runs end-to-end and attributes the
        binding to a host-path component."""
        import json
        import subprocess
        import sys

        out = str(tmp_path / "sat.json")
        p = subprocess.run(
            [sys.executable, "tools/ps_saturation.py", "--iters", "3",
             "--threads", "1,2", "--out", out],
            capture_output=True, text=True, timeout=240,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        assert p.returncode == 0, p.stderr[-1500:]
        rep = json.load(open(out))
        comps = {r["component"] for r in rep["components"]}
        assert {"pull_sparse", "push_sparse", "dense_fwd_bwd"} <= comps
        assert rep["binds_on"] in ("pull_sparse", "push_sparse",
                                   "id_generation")
        assert len(rep["scaling"]) == 2
        assert rep["scaling"][0]["aggregate_examples_per_sec"] > 0
