"""MoE tests: the dropless expert layer (parallel/moe.py).

Oracle: explicit loop-over-experts numpy computation. Mirrors the
reference's moe tests (unittests for moe_layer / global_scatter)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.distributed import mesh as pmesh
from paddle_tpu.parallel.moe import MoELayer, moe_forward, moe_mlp, route

RNG = np.random.RandomState(3)


def _dense_moe_top1(x, gate_w, w1, b1, w2, b2, act=np.tanh):
    """No-drop top-1 oracle: each token goes to its argmax expert."""
    logits = x @ gate_w
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    idx = probs.argmax(-1)
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        e = idx[t]
        h = np.maximum(x[t] @ w1[e] + b1[e], 0)  # relu
        out[t] = probs[t, e] * (h @ w2[e] + b2[e])
    return out


def _dense_moe_gated(x, gate_w, w1, w2, k, held):
    """Renormalised top-k over all experts; only the experts in ``held``
    contribute (their weights sit at index e - held[0])."""
    logits = x @ gate_w
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    f = w2.shape[1]
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        top = np.argsort(-probs[t], kind="stable")[:k]
        for e in top:
            if e not in held:
                continue
            a = x[t] @ w1[e]
            hid = a[:f] / (1 + np.exp(-a[:f])) * a[f:]
            out[t] += probs[t, e] / probs[t, top].sum() * (hid @ w2[e])
    return out


class TestMoEPrimitive:
    def test_top1_matches_dense_oracle(self):
        t, d, h, e = 32, 8, 16, 4
        x = RNG.randn(t, d).astype(np.float32)
        gate_w = RNG.randn(d, e).astype(np.float32)
        w1 = RNG.randn(e, d, h).astype(np.float32) * 0.1
        b1 = RNG.randn(e, h).astype(np.float32) * 0.1
        w2 = RNG.randn(e, h, d).astype(np.float32) * 0.1
        b2 = RNG.randn(e, d).astype(np.float32) * 0.1
        out, aux = moe_mlp(
            jnp.asarray(x), jnp.asarray(gate_w), jnp.asarray(w1),
            jnp.asarray(b1), jnp.asarray(w2), jnp.asarray(b2),
            top_k=1, activation="relu")
        ref = _dense_moe_top1(x, gate_w, w1, b1, w2, b2)
        np.testing.assert_allclose(np.asarray(out._value), ref,
                                   rtol=1e-4, atol=1e-5)
        assert float(aux._value) > 0

    def test_top2_combine_weights_renormalized(self):
        """The top-2 combine weights of each token sum to 1."""
        t, d, h, e = 16, 8, 8, 4
        x = jnp.asarray(RNG.randn(t, d).astype(np.float32))
        gate_w = jnp.asarray(RNG.randn(d, e).astype(np.float32))
        # identity-ish experts: w1=relu passthrough impossible; instead use
        # ones-valued v to read combine mass: expert(x) = 1 vector
        w1 = jnp.zeros((e, d, h), jnp.float32)
        b1 = jnp.ones((e, h), jnp.float32)
        w2 = jnp.zeros((e, h, d), jnp.float32)
        b2 = jnp.ones((e, d), jnp.float32)
        out, _ = moe_mlp(x, gate_w, w1, b1, w2, b2, top_k=2,
                         activation="relu")
        # each expert outputs the all-ones vector, so out = (g1+g2) * ones
        np.testing.assert_allclose(np.asarray(out._value),
                                   np.ones((t, d), np.float32),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("top_k", [1, 2])
    def test_no_token_is_dropped_whatever_the_skew(self, top_k):
        """The capacity dispatch dropped what overflowed an expert's
        buffer (this test used to pin that: capacity=1 lost output
        mass). The dropless layer has no buffer: with a router that
        sends EVERY token to expert 0 first, every token still gets its
        whole combine weight, and expert 0's load is all the tokens."""
        t, d, h, e = 32, 8, 8, 4
        x = jnp.asarray(np.abs(RNG.randn(t, d)).astype(np.float32))
        gate_w = jnp.zeros((d, e), jnp.float32).at[:, 0].set(4.0)
        w1 = jnp.zeros((e, d, h), jnp.float32)
        b1 = jnp.ones((e, h), jnp.float32)
        w2 = jnp.zeros((e, h, d), jnp.float32)
        b2 = jnp.ones((e, d), jnp.float32)
        out, _, stats = moe_forward(x, gate_w, w1, b1, w2, b2, top_k=top_k,
                                    activation="relu",
                                    norm_topk_prob=True)
        # each expert outputs the all-ones vector: out = sum of weights
        np.testing.assert_allclose(np.asarray(out),
                                   np.ones((t, d), np.float32),
                                   rtol=1e-5, atol=1e-5)
        pairs, touched, largest, rows_run = (int(v) for v in stats)
        assert pairs == t * top_k and largest == t
        assert touched == top_k
        assert rows_run == t * top_k        # every pair laid out at once

    def test_topk_gated_matches_dense_oracle(self):
        """top-4 of 16 with renormalised weights, gated experts without
        bias (the SwiGLU form), against a loop over experts."""
        t, d, h, e, k = 24, 8, 16, 16, 4
        x = RNG.randn(t, d).astype(np.float32)
        gate_w = RNG.randn(d, e).astype(np.float32)
        w1 = RNG.randn(e, d, 2 * h).astype(np.float32) * 0.3
        w2 = RNG.randn(e, h, d).astype(np.float32) * 0.3
        out, _, stats = moe_forward(
            jnp.asarray(x), jnp.asarray(gate_w), jnp.asarray(w1), None,
            jnp.asarray(w2), None, top_k=k, activation="silu", gated=True)
        want = _dense_moe_gated(x, gate_w, w1, w2, k, range(e))
        np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4,
                                   atol=1e-5)
        assert int(stats[0]) == t * k

    @pytest.mark.parametrize("cuts", [(0, 8, 16), (0, 4, 12, 16)])
    def test_shares_add_up_to_the_whole_layer(self, cuts):
        """Each share routes over all 16 experts and computes its own
        experts' part; the parts add up to the uncut layer, and their
        pair counts to tokens x top_k."""
        t, d, h, e, k = 24, 8, 16, 16, 4
        x = jnp.asarray(RNG.randn(t, d).astype(np.float32))
        gate_w = jnp.asarray(RNG.randn(d, e).astype(np.float32))
        w1 = jnp.asarray(RNG.randn(e, d, 2 * h).astype(np.float32) * 0.3)
        w2 = jnp.asarray(RNG.randn(e, h, d).astype(np.float32) * 0.3)
        whole, _, _ = moe_forward(x, gate_w, w1, None, w2, None, top_k=k,
                                  activation="silu", gated=True)
        total, pairs = 0.0, 0
        for lo, hi in zip(cuts, cuts[1:]):
            part, _, stats = moe_forward(
                x, gate_w, w1[lo:hi], None, w2[lo:hi], None, top_k=k,
                lo=lo, activation="silu", gated=True)
            want = _dense_moe_gated(np.asarray(x), np.asarray(gate_w),
                                    np.asarray(w1), np.asarray(w2), k,
                                    range(lo, hi))
            np.testing.assert_allclose(np.asarray(part), want, rtol=1e-4,
                                       atol=1e-5)
            total = total + part
            pairs += int(stats[0])
        np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                                   rtol=1e-4, atol=1e-5)
        assert pairs == t * k


def _loop_group_router(x, gate_w, k, n_group, topk_group, scaling,
                       renormalise):
    """Group-limited greedy routing, one token and one group at a time:
    {expert: weight} a token."""
    logits = x @ gate_w
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    size = probs.shape[1] // n_group
    out = []
    for p in probs:
        scores = [max(p[g * size:(g + 1) * size]) for g in range(n_group)]
        groups = sorted(range(n_group), key=lambda g: -scores[g])[
            :topk_group]
        allowed = [e for g in groups for e in range(g * size,
                                                    (g + 1) * size)]
        chosen = sorted(allowed, key=lambda e: -p[e])[:k]
        total = sum(p[e] for e in chosen) if renormalise else 1.0
        out.append({e: p[e] / total * scaling for e in chosen})
    return out


class TestGroupLimitedRouting:
    @pytest.mark.parametrize("n_group,topk_group,k,scaling,renormalise", [
        (8, 3, 6, 16.0, False),     # DeepSeek-V2's
        (4, 2, 3, 1.0, True),
        (4, 4, 5, 2.5, False),      # every group kept: plain top-k
        (2, 1, 4, 1.0, False),
    ])
    def test_route_matches_a_loop_written_router(self, n_group, topk_group,
                                                 k, scaling, renormalise):
        rng = np.random.RandomState(n_group * 10 + k)
        x = rng.randn(50, 12).astype(np.float32)
        gate_w = rng.randn(12, 32).astype(np.float32)
        weights, experts, _ = route(
            jnp.asarray(x), jnp.asarray(gate_w), k, renormalise, n_group,
            topk_group, scaling)
        want = _loop_group_router(x, gate_w, k, n_group, topk_group,
                                  scaling, renormalise)
        for w_row, e_row, ref in zip(np.asarray(weights),
                                     np.asarray(experts), want):
            assert sorted(e_row.tolist()) == sorted(ref)
            np.testing.assert_allclose(
                w_row, [ref[e] for e in e_row.tolist()], rtol=1e-5)
            assert len({e // (32 // n_group) for e in e_row}) <= topk_group

    def test_defaults_are_the_plain_router(self):
        """One group, factor 1: the program of every model that does not
        ask for groups is what it was."""
        x = jnp.asarray(RNG.randn(9, 8).astype(np.float32))
        gate_w = jnp.asarray(RNG.randn(8, 16).astype(np.float32))
        plain = jax.make_jaxpr(lambda a, b: route(a, b, 4, True))(x, gate_w)
        asked = jax.make_jaxpr(
            lambda a, b: route(a, b, 4, True, 1, 1, 1.0))(x, gate_w)
        assert str(plain) == str(asked)
        assert "reshape" not in str(plain)

    def test_groups_that_do_not_divide_the_experts_are_refused(self):
        x = jnp.zeros((2, 8), jnp.float32)
        with pytest.raises(ValueError, match="groups"):
            route(x, jnp.zeros((8, 16), jnp.float32), 2, False, 3, 2)

    def test_group_limited_shares_add_up_to_the_whole_layer(self):
        """The eight groups' parts, each routed over all 32 experts with
        the group limit, add up to the uncut layer."""
        t, d, h, e, k = 24, 8, 16, 32, 6
        x = jnp.asarray(RNG.randn(t, d).astype(np.float32))
        gate_w = jnp.asarray(RNG.randn(d, e).astype(np.float32))
        w1 = jnp.asarray(RNG.randn(e, d, 2 * h).astype(np.float32) * 0.3)
        w2 = jnp.asarray(RNG.randn(e, h, d).astype(np.float32) * 0.3)
        kw = dict(top_k=k, activation="silu", gated=True,
                  norm_topk_prob=False, n_group=8, topk_group=3,
                  routed_scaling_factor=16.0)
        whole, _, _ = moe_forward(x, gate_w, w1, None, w2, None, **kw)
        total, pairs = 0.0, 0
        for lo in range(0, e, 4):
            part, _, stats = moe_forward(x, gate_w, w1[lo:lo + 4], None,
                                         w2[lo:lo + 4], None, lo=lo, **kw)
            total = total + part
            pairs += int(stats[0])
        np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                                   rtol=1e-4, atol=1e-4)
        assert pairs == t * k


# the softmax router's program, primitive by primitive, as PR 35 left it:
# what the two sparse cells of the benchmark trace
PLAIN_ROUTE = [
    "dot_general", "reduce_max", "max", "broadcast_in_dim",
    "stop_gradient", "sub", "exp", "reduce_sum", "broadcast_in_dim", "div",
    "top_k", "reduce_sum", "broadcast_in_dim", "max", "div", "slice",
    "squeeze", "jit", "reduce_sum", "div", "reduce_sum", "div", "mul",
    "reduce_sum", "mul"]
GROUPED_ROUTE = [
    "dot_general", "reduce_max", "max", "broadcast_in_dim",
    "stop_gradient", "sub", "exp", "reduce_sum", "broadcast_in_dim", "div",
    "reshape", "reduce_max", "top_k", "broadcast_in_dim", "iota",
    "broadcast_in_dim", "eq", "reduce_or", "broadcast_in_dim", "jit",
    "reshape", "top_k", "mul", "slice", "squeeze", "jit", "reduce_sum",
    "div", "reduce_sum", "div", "mul", "reduce_sum", "mul"]


def _primitives(fn, *args):
    return [e.primitive.name for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns]


class TestSigmoidBiasRouting:
    """``route(select_bias=...)``: sigmoid scores, the bias in the
    choice and out of the weights (Nemotron-H's router)."""

    def _inputs(self, t=50, d=12, e=32, seed=0):
        rng = np.random.RandomState(seed)
        return (rng.randn(t, d).astype(np.float32),
                rng.randn(d, e).astype(np.float32),
                rng.uniform(-0.3, 0.3, e).astype(np.float32))

    @pytest.mark.parametrize("k,scaling,renormalise", [
        (6, 2.5, True), (3, 1.0, False), (1, 2.0, True)])
    def test_route_matches_a_router_written_apart(self, k, scaling,
                                                  renormalise):
        x, gate_w, bias = self._inputs(seed=k)
        weights, experts, _ = route(
            jnp.asarray(x), jnp.asarray(gate_w), k, renormalise,
            routed_scaling_factor=scaling, select_bias=jnp.asarray(bias))
        scores = 1.0 / (1.0 + np.exp(-(x @ gate_w)))
        chosen = np.argsort(-(scores + bias), axis=-1)[:, :k]
        want = np.take_along_axis(scores, chosen, -1)
        if renormalise:
            want = want / want.sum(-1, keepdims=True)
        assert np.array_equal(np.asarray(experts), chosen)
        np.testing.assert_allclose(np.asarray(weights), want * scaling,
                                   rtol=1e-5)

    def test_bias_changes_the_choice_and_not_the_weights(self):
        x, gate_w, bias = self._inputs(seed=7)
        x, gate_w = jnp.asarray(x), jnp.asarray(gate_w)
        none = jnp.zeros((32,), jnp.float32)
        pushed = none.at[5].set(10.0)       # expert 5 always wins a place
        w0, e0, _ = route(x, gate_w, 6, True, routed_scaling_factor=2.5,
                          select_bias=none)
        w1, e1, _ = route(x, gate_w, 6, True, routed_scaling_factor=2.5,
                          select_bias=pushed)
        assert not np.array_equal(np.asarray(e0), np.asarray(e1))
        assert (np.asarray(e1)[:, 0] == 5).all()
        # its weight is its own score's share, not the pushed one's: no
        # weight is anywhere near 10, and a row's weights sum to the
        # scaling factor
        scores = np.asarray(jax.nn.sigmoid(x @ gate_w))
        picked = np.take_along_axis(scores, np.asarray(e1), -1)
        np.testing.assert_allclose(
            np.asarray(w1), 2.5 * picked / picked.sum(-1, keepdims=True),
            rtol=1e-5)
        np.testing.assert_allclose(np.asarray(w1).sum(-1), 2.5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(w0).sum(-1), 2.5, rtol=1e-5)

    def test_a_bias_with_group_limited_routing_is_refused(self):
        x, gate_w, bias = self._inputs()
        with pytest.raises(ValueError, match="selection bias"):
            route(jnp.asarray(x), jnp.asarray(gate_w), 4, True, 4, 2,
                  select_bias=jnp.asarray(bias))

    def test_ungated_relu2_experts_match_a_dense_oracle(self):
        """``moe_forward(gated=False, activation="relu2")`` with the
        sigmoid router: every expert on every token, weighted by the
        router, is the same sum; two halves add up to it."""
        t, d, h, e, k = 24, 8, 16, 8, 3
        rng = np.random.RandomState(2)
        x = jnp.asarray(rng.randn(t, d).astype(np.float32))
        gate_w = jnp.asarray(rng.randn(d, e).astype(np.float32))
        bias = jnp.asarray(rng.uniform(-0.3, 0.3, e).astype(np.float32))
        w1 = jnp.asarray(rng.randn(e, d, h).astype(np.float32) * 0.3)
        w2 = jnp.asarray(rng.randn(e, h, d).astype(np.float32) * 0.3)
        kw = dict(top_k=k, activation="relu2", gated=False,
                  norm_topk_prob=True, routed_scaling_factor=2.5,
                  select_bias=bias)
        whole, _, stats = moe_forward(x, gate_w, w1, None, w2, None, **kw)
        weights, experts, _ = route(x, gate_w, k, True,
                                    routed_scaling_factor=2.5,
                                    select_bias=bias)
        dense = jnp.zeros((t, e)).at[jnp.arange(t)[:, None], experts].set(
            weights)
        hid = jnp.square(jax.nn.relu(jnp.einsum("td,edf->etf", x, w1)))
        want = jnp.einsum("te,etd->td", dense,
                          jnp.einsum("etf,efd->etd", hid, w2))
        np.testing.assert_allclose(np.asarray(whole), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)
        assert int(stats[0]) == t * k
        halves = sum(moe_forward(x, gate_w, w1[lo:lo + 4], None,
                                 w2[lo:lo + 4], None, lo=lo, **kw)[0]
                     for lo in (0, 4))
        np.testing.assert_allclose(np.asarray(halves), np.asarray(whole),
                                   rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_the_softmax_path_traces_to_what_it_did(self, dtype):
        """No bias: the router of the sparse cells the benchmark already
        has is the program it was, plain and group-limited."""
        x = jnp.zeros((9, 8), dtype)
        gate_w = jnp.zeros((8, 16), dtype)
        assert _primitives(lambda a, b: route(a, b, 4, True),
                           x, gate_w) == PLAIN_ROUTE
        assert _primitives(
            lambda a, b: route(a, b, 6, False, 8, 3, 16.0),
            x, gate_w) == GROUPED_ROUTE
        plain = jax.make_jaxpr(lambda a, b: route(a, b, 4, True))(x, gate_w)
        asked = jax.make_jaxpr(lambda a, b: route(
            a, b, 4, True, 1, 1, 1.0, select_bias=None))(x, gate_w)
        assert str(plain) == str(asked)
        assert "logistic" not in str(plain)


# what follows the router in a layer that lays out every pair, primitive
# by primitive, as PR 37 left it but for the fourth counter's
# ``broadcast_in_dim``: what a decode step of the three sparse cells traces
def _every_pair_tail(top_k, activation):
    return ([
        "ge", "lt", "and", "sub", "jit", "reshape", "jit", "lt", "add",
        "select_n", "broadcast_in_dim", "gather", "iota", "jit", "slice",
        "slice", "sub", "jit", "lt", "add", "select_n", "broadcast_in_dim",
        "gather", "ragged_dot_general", "min"] + activation + [
        "ragged_dot_general", "broadcast_in_dim", "iota", "lt", "add",
        "select_n", "broadcast_in_dim", "scatter", "reshape",
        "broadcast_in_dim"] + top_k * [
        "slice", "squeeze", "lt", "add", "select_n", "broadcast_in_dim",
        "gather", "convert_element_type", "broadcast_in_dim", "gather",
        "broadcast_in_dim", "mul", "broadcast_in_dim", "gather",
        "broadcast_in_dim", "jit", "add"] + [
        "convert_element_type", "convert_element_type", "reduce_sum", "gt",
        "convert_element_type", "reduce_sum", "reduce_max",
        "broadcast_in_dim", "broadcast_in_dim", "broadcast_in_dim",
        "broadcast_in_dim", "concatenate", "convert_element_type"])


GATED_SILU = ["slice", "jit", "slice", "mul"]
RELU2 = ["custom_jvp_call", "square"]
SIGMOID_ROUTE = [
    "dot_general", "logistic", "broadcast_in_dim", "add", "top_k", "jit",
    "reduce_sum", "broadcast_in_dim", "add", "div", "mul", "slice",
    "squeeze", "jit", "reduce_sum", "div", "reduce_sum", "div", "mul",
    "reduce_sum", "mul"]


def _share_oracle(x, gate_w, w1, b1, w2, b2, k, held, gated, activation):
    """Softmax top-k renormalised over all experts, a token and an
    expert at a time; only the experts in ``held`` contribute."""
    logits = x @ gate_w
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        top = np.argsort(-probs[t], kind="stable")[:k]
        for e in top:
            if e not in held:
                continue
            i = e - held[0]
            a = x[t] @ w1[i] + (0 if b1 is None else b1[i])
            if gated:
                f = a.shape[0] // 2
                a = a[:f] / (1 + np.exp(-a[:f])) * a[f:]
            else:
                a = np.square(np.maximum(a, 0))
            y = a @ w2[i] + (0 if b2 is None else b2[i])
            out[t] += probs[t, e] / probs[t, top].sum() * y
    return out


class TestPairBlock:
    """``moe_forward`` over a share of the experts and more pairs than
    one block: the layer lays out the first block of the sorted pair
    list where every pair that lands here is in it, every pair where
    not."""

    E, K, LO, HELD, BLOCK, T = 8, 2, 2, 3, 8, 20       # 40 pairs

    def _chosen(self, live):
        """[T, 2] experts a token, ``live`` of the 40 pairs on the held
        experts 2..4; "one": every pair that lands here on expert 3."""
        away = [(0, 1), (5, 6), (7, 0), (1, 6)]
        chosen = [list(away[t % 4]) for t in range(self.T)]
        if live == "one":
            for t in range(self.T):
                chosen[t][t % 2] = 3
            return chosen
        left = live
        for t in range(self.T):         # first choices, then second
            if left:
                chosen[t][0] = 2 + t % 3
                left -= 1
        for t in range(self.T):
            if left:
                chosen[t][1] = 2 + (t + 1) % 3
                left -= 1
        return chosen

    def _inputs(self, chosen, bias, gated, seed=0):
        rng = np.random.RandomState(seed)
        e, d, h = self.E, self.E, 6
        # the router reads a token's choices off its features
        x = rng.randn(self.T, d).astype(np.float32) * 0.1
        for t, (first, second) in enumerate(chosen):
            x[t, first] += 6.0
            x[t, second] += 5.0
        gate_w = np.eye(d, e, dtype=np.float32)
        w1 = rng.randn(self.HELD, d, 2 * h if gated else h).astype(
            np.float32) * 0.3
        w2 = rng.randn(self.HELD, h, d).astype(np.float32) * 0.3
        b1 = b2 = None
        if bias:
            b1 = rng.randn(*w1.shape[::2]).astype(np.float32) * 0.3
            b2 = rng.randn(self.HELD, d).astype(np.float32) * 0.3
        return x, gate_w, w1, b1, w2, b2

    @staticmethod
    def _forward(arrays, block, monkeypatch, **kw):
        from paddle_tpu.parallel import moe

        monkeypatch.setattr(moe, "_pair_block", lambda *shapes: block)
        return moe_forward(*(None if a is None else jnp.asarray(a)
                             for a in arrays), **kw)

    @pytest.mark.parametrize("bias", [False, True], ids=["nobias", "bias"])
    @pytest.mark.parametrize("gated", [True, False], ids=["silu", "relu2"])
    @pytest.mark.parametrize("live,fits", [
        (0, True),          # nothing held is chosen
        (1, True),          # one row
        (8, True),          # exactly one block
        (9, False),         # one row over a block
        (40, False),        # every pair lands here
        ("one", False),     # all 20 pairs that land here on one expert
    ])
    def test_a_block_matches_the_oracle_and_every_pair_laid_out(
            self, live, fits, gated, bias, monkeypatch):
        arrays = self._inputs(self._chosen(live), bias, gated)
        kw = dict(top_k=self.K, lo=self.LO, gated=gated,
                  activation="silu" if gated else "relu2")
        blocked, _, stats = self._forward(arrays, self.BLOCK, monkeypatch,
                                          **kw)
        once, _, stats_once = self._forward(arrays, 10 ** 6, monkeypatch,
                                            **kw)
        want = _share_oracle(*arrays, self.K,
                             range(self.LO, self.LO + self.HELD), gated,
                             kw["activation"])
        np.testing.assert_allclose(np.asarray(blocked), want, rtol=1e-4,
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(blocked), np.asarray(once),
                                   rtol=1e-4, atol=1e-5)
        pairs = 20 if live == "one" else live
        assert int(stats[0]) == pairs
        assert stats[:3].tolist() == stats_once[:3].tolist()
        # the rows handed to the grouped matmuls: a block where it held
        # every pair that landed here, else every pair
        assert int(stats[3]) == (self.BLOCK if fits else self.T * self.K)
        assert int(stats_once[3]) == self.T * self.K

    def test_a_layer_that_holds_every_expert_is_one_program(
            self, monkeypatch):
        """No pair can land elsewhere, so a block saves nothing: the
        layer lays every pair out whatever their number, with no
        ``cond``."""
        rng = np.random.RandomState(1)
        x = rng.randn(self.T, 8).astype(np.float32)
        gate_w = rng.randn(8, self.E).astype(np.float32)
        w1 = rng.randn(self.E, 8, 12).astype(np.float32) * 0.3
        w2 = rng.randn(self.E, 6, 8).astype(np.float32) * 0.3
        out, _, stats = self._forward(
            (x, gate_w, w1, None, w2, None), self.BLOCK, monkeypatch,
            top_k=self.K, gated=True, activation="silu")
        np.testing.assert_allclose(
            np.asarray(out), _share_oracle(x, gate_w, w1, None, w2, None,
                                           self.K, range(self.E), True,
                                           "silu"), rtol=1e-4, atol=1e-5)
        assert stats.tolist()[0::3] == [self.T * self.K, self.T * self.K]
        text = str(jax.make_jaxpr(lambda *a: moe_forward(
            *a[:3], None, a[3], None, top_k=self.K, gated=True,
            activation="silu"))(*map(jnp.asarray, (x, gate_w, w1, w2))))
        assert "cond[" not in text

    @pytest.mark.parametrize("live,rows", [(6, 8), (13, 40)],
                             ids=["a-block", "every-pair"])
    def test_gradient_through_a_share(self, live, rows, monkeypatch):
        """Reverse mode through the ``cond`` and either of its programs,
        against ``jax.grad`` of every held expert on every token
        weighted by the router."""
        arrays = self._inputs(self._chosen(live), True, True, seed=4)
        arrays = [jnp.asarray(a) for a in arrays]
        held = self.HELD

        def shared(x, gate_w, w1, b1, w2, b2):
            out, aux, stats = moe_forward(
                x, gate_w, w1, b1, w2, b2, top_k=self.K, lo=self.LO,
                gated=True, activation="silu")
            return jnp.sum(jnp.sin(out)) + 0.1 * aux, stats

        def dense(x, gate_w, w1, b1, w2, b2):
            weights, experts, aux = route(x, gate_w, self.K, True)
            table = jnp.zeros((self.T, self.E)).at[
                jnp.arange(self.T)[:, None], experts].set(weights)
            a = jnp.einsum("td,edf->etf", x, w1) + b1[:, None]
            f = a.shape[-1] // 2
            hid = jax.nn.silu(a[..., :f]) * a[..., f:]
            y = jnp.einsum("etf,efd->etd", hid, w2) + b2[:, None]
            out = jnp.einsum("te,etd->td",
                             table[:, self.LO:self.LO + held], y)
            return jnp.sum(jnp.sin(out)) + 0.1 * aux

        from paddle_tpu.parallel import moe

        monkeypatch.setattr(moe, "_pair_block", lambda *shapes: self.BLOCK)
        (value, stats), got = jax.value_and_grad(
            shared, argnums=range(6), has_aux=True)(*arrays)
        assert stats.tolist()[0::3] == [live, rows]
        want_value, want = jax.value_and_grad(dense, argnums=range(6))(
            *arrays)
        np.testing.assert_allclose(float(value), float(want_value),
                                   rtol=1e-5)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize(
        "slots,e,held,k,router,activation,kw", [
            (128, 160, 20, 6, GROUPED_ROUTE, GATED_SILU,
             dict(gated=True, activation="silu", norm_topk_prob=False,
                  n_group=8, topk_group=3, routed_scaling_factor=16.0)),
            (128, 512, 256, 10, PLAIN_ROUTE, GATED_SILU,
             dict(gated=True, activation="silu", norm_topk_prob=True)),
            (256, 128, 64, 6, SIGMOID_ROUTE, RELU2,
             dict(activation="relu2", norm_topk_prob=True,
                  routed_scaling_factor=2.5,
                  select_bias=jnp.zeros((128,), jnp.float32))),
        ], ids=["deepseek-v2", "qwen3-next", "nemotron-h"])
    def test_a_decode_step_traces_to_the_program_it_was(
            self, slots, e, held, k, router, activation, kw):
        """The pairs of a decode step of the three sparse cells (128 x
        6, 128 x 10, 256 x 6) are at most one block: the layer lays out
        every pair, the program it was with one more counter behind the
        three."""
        from paddle_tpu.parallel.moe import _pair_block

        assert slots * k <= _pair_block(slots * k, held, e)
        x = jnp.zeros((slots, 64), jnp.bfloat16)
        gate_w = jnp.zeros((64, e), jnp.bfloat16)
        w1 = jnp.zeros((held, 64, 64 if kw.get("gated") else 32),
                       jnp.bfloat16)
        w2 = jnp.zeros((held, 32, 64), jnp.bfloat16)
        got = _primitives(lambda *a: moe_forward(
            *a[:3], None, a[3], None, top_k=k, **kw), x, gate_w, w1, w2)
        assert got == router + _every_pair_tail(k, activation)

    @pytest.mark.parametrize("held,e,k,blocks", [
        # rows of a prefill bucket -> pairs a block, by family
        (20, 160, 6, {1024: 2048, 4096: 4096, 8192: 8192}),
        (256, 512, 10, {1024: 6912, 2048: 16384, 8192: 54656}),
        (64, 128, 6, {1024: 4096, 4096: 16384, 8192: 32768}),
    ], ids=["deepseek-v2", "qwen3-next", "nemotron-h"])
    def test_a_block_is_a_third_over_the_share_held_in_whole_tiles(
            self, held, e, k, blocks):
        """A block comes from shapes alone: a third more than the held
        experts' share of the pairs, whole 128-row tiles, and
        ``moe_gmm`` on the row tile it would pick for every pair."""
        from paddle_tpu.kernels.moe_gmm import row_tile
        from paddle_tpu.parallel.moe import _pair_block

        for rows, block in blocks.items():
            assert _pair_block(rows * k, held, e) == block
            assert block % 128 == 0 and block >= 4 * rows * k * held / (3 * e)
            assert row_tile(block, held) == row_tile(rows * k, held)


class TestMoeGmmKernel:
    """kernels/moe_gmm.py in interpret mode against jax.lax.ragged_dot
    (the rows of no group are undefined and not compared)."""

    @pytest.mark.parametrize("m,k,n,sizes", [
        (96, 64, 128, [10, 0, 50, 20]),          # an empty group, a tail
        (256, 32, 256, [0] * 8),                 # nothing routed here
        (640, 64, 128, [100, 0, 3, 200, 1, 0, 136, 200]),    # 128-row tiles
        (40, 16, 24, [5, 30, 5]),                # M padded to the tile
        (64, 16, 128, [64]),                     # one group, whole tiles
        # a width the lanes cannot tile under a K they can (1856 under
        # 2688): the matrices are taken transposed, whole
        (96, 128, 72, [10, 0, 50, 20]),
        (640, 128, 24, [100, 0, 3, 200, 1, 0, 136, 200]),
    ])
    def test_kernel_matches_ragged_dot(self, m, k, n, sizes):
        from paddle_tpu.kernels import moe_gmm as mg

        lhs = jnp.asarray(RNG.randn(m, k).astype(np.float32))
        rhs = jnp.asarray(RNG.randn(len(sizes), k, n).astype(np.float32))
        gs = jnp.asarray(sizes, jnp.int32)
        got = mg.moe_gmm(lhs, rhs, gs, interpret=True)
        want = jax.lax.ragged_dot(lhs, rhs, gs)
        total = sum(sizes)
        assert got.shape == (m, n)
        np.testing.assert_allclose(np.asarray(got[:total]),
                                   np.asarray(want[:total]), rtol=1e-5,
                                   atol=1e-5)

    def test_visit_list_skips_empty_groups(self):
        from paddle_tpu.kernels import moe_gmm as mg

        sizes = jnp.asarray([40, 0, 0, 30, 0, 58], jnp.int32)
        offsets, gid, tile, visits = mg.visit_list(sizes, 128, 32)
        n = int(visits[0])
        # rows 0-39 | 40-69 | 70-127 over tiles of 32: group 0 touches
        # tiles 0, 1; group 3 tiles 1, 2; group 5 tiles 2, 3
        assert n == 6
        assert list(zip(gid[:n].tolist(), tile[:n].tolist())) == [
            (0, 0), (0, 1), (3, 1), (3, 2), (5, 2), (5, 3)]
        # the tail repeats the last visit: a grid step and no DMA
        assert gid.shape == (128 // 32 + 6 - 1,)
        assert set(zip(gid[n:].tolist(), tile[n:].tolist())) == {(5, 3)}
        assert offsets.tolist() == [0, 40, 40, 40, 70, 70, 128]

    def test_kernel_backward_is_ragged_dots(self):
        from paddle_tpu.kernels import moe_gmm as mg

        lhs = jnp.asarray(RNG.randn(96, 16).astype(np.float32))
        rhs = jnp.asarray(RNG.randn(3, 16, 24).astype(np.float32))
        gs = jnp.asarray([30, 0, 66], jnp.int32)

        def loss(fn):
            return lambda a, b: jnp.sum(jnp.sin(fn(a, b, gs)))

        got = jax.grad(loss(mg._gmm_with_ragged_vjp), (0, 1))(lhs, rhs)
        want = jax.grad(loss(jax.lax.ragged_dot), (0, 1))(lhs, rhs)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)


class TestMoELayer:
    def test_forward_backward(self):
        paddle.seed(0)
        moe = MoELayer(d_model=16, d_hidden=32, num_experts=4, top_k=2,
                       gate="gshard")
        x = paddle.to_tensor(RNG.randn(4, 8, 16).astype(np.float32))
        x.stop_gradient = False
        out = moe(x)
        assert out.shape == [4, 8, 16]
        assert moe.aux_loss is not None
        loss = (out * out).sum() + moe.aux_loss * 0.01
        loss.backward()
        for n, p in moe.named_parameters():
            assert p.grad is not None, "no grad for %s" % n
            assert np.isfinite(p.grad.numpy()).all(), n

    def test_switch_gate_is_top1(self):
        moe = MoELayer(16, 32, 4, gate="switch")
        assert moe.top_k == 1

    def test_training_reduces_loss(self):
        paddle.seed(1)
        moe = MoELayer(d_model=8, d_hidden=16, num_experts=2, top_k=1,
                       gate="switch")
        opt = paddle.optimizer.Adam(learning_rate=5e-3,
                                    parameters=moe.parameters())
        x = paddle.to_tensor(RNG.randn(16, 8).astype(np.float32))
        y = paddle.to_tensor(RNG.randn(16, 8).astype(np.float32))
        losses = []
        for _ in range(25):
            out = moe(x)
            loss = ((out - y) ** 2).mean() + moe.aux_loss * 0.01
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss))
        assert losses[-1] < losses[0]


class TestMoESharded:
    def test_expert_parallel_on_mesh(self):
        """MoE inside a jit over the 8-device mesh: the layer computes
        its experts where the rows are (no exchange between chips), and
        the result matches the single-device run."""
        mesh = pmesh.build_hybrid_mesh(dp=8, mp=1)
        paddle.seed(0)
        moe = MoELayer(d_model=16, d_hidden=32, num_experts=8, top_k=1,
                       gate="switch")
        x_np = RNG.randn(32, 16).astype(np.float32)
        out_eager = moe(paddle.to_tensor(x_np)).numpy()

        names, values = moe.functional_state()

        def fn(vals, xv):
            out = moe.functional_call(vals, paddle.Tensor(xv),
                                      state_names=names)
            return out._value

        from jax.sharding import NamedSharding, PartitionSpec as P

        with mesh:
            out_jit = jax.jit(fn)(values, jnp.asarray(x_np))
        np.testing.assert_allclose(np.asarray(out_jit), out_eager,
                                   rtol=1e-4, atol=1e-5)

    def test_global_scatter_roundtrip(self):
        from paddle_tpu.distributed import collective
        from paddle_tpu.parallel.moe import global_gather, global_scatter

        pmesh.build_hybrid_mesh(dp=8, mp=1)
        x = paddle.to_tensor(
            np.arange(256, dtype=np.float32).reshape(64, 4))
        g = collective.Group(axis="dp")
        y = global_scatter(x, group=g)
        # the exchange is a (src, dst) chunk transpose, and an involution
        assert not np.allclose(y.numpy(), x.numpy())
        z = global_gather(y, group=g)
        np.testing.assert_allclose(z.numpy(), x.numpy())


class TestGPTMoE:
    def test_gpt_moe_trains(self):
        from paddle_tpu.models.gpt import GPTModel

        paddle.seed(0)
        m = GPTModel(vocab_size=128, hidden_size=32, num_layers=2,
                     num_heads=2, max_seq_len=32, moe_experts=4,
                     moe_every=2, moe_top_k=1)
        assert any(getattr(b, "is_moe", False) for b in m.blocks)
        opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                    parameters=m.parameters())
        ids = paddle.to_tensor(RNG.randint(0, 128, (2, 16)).astype("int64"))
        losses = []
        for _ in range(8):
            loss = m(ids, labels=ids)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss))
        assert losses[-1] < losses[0]
