"""Mixture-of-Experts: one dropless layer that is told which experts it
holds.

Parity: reference MoELayer
(/root/reference/python/paddle/incubate/distributed/models/moe/moe_layer.py:260)
with its gates (gate/gshard_gate.py, switch_gate.py, naive_gate.py).

The layer routes every token over ALL ``num_experts`` (the published
router width), keeps the pairs (token, expert) whose expert lies in
``experts_held = range(lo, hi)``, sorts them by expert and runs the
held experts as two grouped matmuls over the sorted rows (in, then
out): the Mosaic kernel ``moe_gmm`` on a TPU, ``jax.lax.ragged_dot``
elsewhere (kernels/moe_gmm.py). No capacity, so no token is dropped and
no expert is padded; an expert that received no row costs nothing. What
the experts held elsewhere would have added is LEFT OUT of the result:
with ``experts_held`` the whole range that is nothing, with a share it
is the partial sum a chip of an expert-parallel deployment computes
before the exchange, and the layer pays for the pairs that land here,
not for every pair of every token: a prefill lays out one block of the
sorted pair list, sized to hold the pairs of the experts held, so its
forward gather, its activation and the kernel's grid are a block's
(``_pair_block``; every pair is laid out instead where a skewed routing
puts more than a block here, so nothing is dropped). A decode step,
whose pairs are a block at most, and a layer that holds every expert lay
all T * k pairs out. The exchange itself (all-to-all between the chips
that share a layer) is not here; nothing stands in for it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core.dispatch import primitive
from ..kernels.moe_gmm import ROWS_A_GROUP, grouped_matmul
from ..nn import initializer as I
from ..nn.layer import Layer

_A = jnp.asarray
_ACTIVATIONS = {"gelu": jax.nn.gelu, "relu": jax.nn.relu,
                "relu2": lambda x: jnp.square(jax.nn.relu(x)),
                "silu": jax.nn.silu}


def route(x, gate_w, top_k, norm_topk_prob, n_group=1, topk_group=1,
          routed_scaling_factor=1.0, select_bias=None):
    """The router in float32 over every published expert.
    x [T, D], gate_w [D, E] -> (weights [T, k], experts [T, k] int32,
    aux): the top-k probabilities (renormalised to sum 1 when
    ``norm_topk_prob``, then times ``routed_scaling_factor``), their
    experts, and the load-balance loss
    E * sum_e (mean probability of e) * (share of first choices e).
    With ``n_group`` > 1 the choice is group-limited: the experts lie in
    ``n_group`` equal contiguous groups, a group scores its largest
    probability, and the top-k is taken inside the ``topk_group`` best
    groups only (so a token's experts lie on at most ``topk_group`` of
    the devices that hold a group each).

    Without ``select_bias`` an expert's probability is its softmax. With
    one ([E], the published ``e_score_correction_bias``) it is
    ``sigmoid`` of its own logit, the top-k is taken of probability +
    bias, and the weights are the probabilities of the chosen WITHOUT
    the bias, over their sum + 1e-20 when normalised: the bias steers
    the load and stays out of the result."""
    gate_w = _A(gate_w)
    if x.dtype == jnp.float32 or gate_w.dtype == jnp.float32:
        logits = jnp.dot(x.astype(jnp.float32), gate_w.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
    else:
        # 16-bit operands: their products are exact in float32, so one
        # native pass with a float32 accumulator IS the float32 matmul
        logits = jnp.dot(x, gate_w, preferred_element_type=jnp.float32,
                         precision=jax.lax.Precision.DEFAULT)
    e = logits.shape[-1]
    if select_bias is not None:
        if n_group > 1:
            raise ValueError("route: a selection bias with group-limited "
                             "routing is not written")
        probs = jax.nn.sigmoid(logits)
        _, experts = jax.lax.top_k(
            probs + _A(select_bias).astype(jnp.float32), top_k)
        weights = jnp.take_along_axis(probs, experts, axis=-1)
        if norm_topk_prob:
            weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                                 + 1e-20)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        candidates = probs
        if n_group > 1:
            if e % n_group or not 0 < topk_group <= n_group:
                raise ValueError("route: %d experts in %d groups, top %d "
                                 "of them" % (e, n_group, topk_group))
            grouped = probs.reshape(-1, n_group, e // n_group)
            _, best = jax.lax.top_k(jnp.max(grouped, axis=-1), topk_group)
            kept = jnp.any(best[:, :, None] == jnp.arange(n_group), axis=1)
            candidates = jnp.where(kept[:, :, None], grouped,
                                   0.0).reshape(probs.shape)
        weights, experts = jax.lax.top_k(candidates, top_k)
        if norm_topk_prob:
            weights = weights / jnp.maximum(
                jnp.sum(weights, axis=-1, keepdims=True), 1e-9)
    if routed_scaling_factor != 1.0:
        weights = weights * routed_scaling_factor
    first = jax.nn.one_hot(experts[:, 0], e, dtype=probs.dtype)
    aux = e * jnp.sum(jnp.mean(probs, axis=0) * jnp.mean(first, axis=0))
    return weights, experts.astype(jnp.int32), aux


def balance_select_bias(x, gate_w, select_bias, top_k, rounds, step):
    """``rounds`` updates of a router's ``select_bias`` [E] by the rule
    that keeps it in training (auxiliary-loss-free balancing, Wang et
    al., arXiv:2408.15664): with the sigmoid scores of the rows ``x``
    [rows, D] fixed, every expert's bias moves by the round's step
    (``step`` falling to a fortieth of it) toward the mean load: up if
    the top-k of score + bias sent it fewer rows than the mean, down if
    more. -> the new bias, in its own dtype."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), _A(gate_w).astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    experts = scores.shape[-1]

    def update(r, bias):
        _, chosen = jax.lax.top_k(scores + bias, top_k)
        load = jnp.zeros((experts,), jnp.float32).at[
            chosen.reshape(-1)].add(1.0)
        size = step * (1.0 - 0.975 * r / rounds)
        return bias + size * jnp.sign(jnp.mean(load) - load)

    bias = _A(select_bias)
    return jax.lax.fori_loop(0, rounds, update,
                             bias.astype(jnp.float32)).astype(bias.dtype)


def balance_router_biases(model, blocks, run, input_ids, rounds, step):
    """What training does to the selection bias of each expert block of
    ``blocks`` (a layer with ``balance(rows, rounds, step)``), done here
    on ``input_ids`` [B, T]: one jitted ``run(ids)`` of ``model`` with
    its weights bound and each block's input caught on its way in, then
    every block's ``balance`` on the rows it caught. A published
    selection bias exists to keep the experts' loads even; a router of
    seeded random weights sends every token to much the same few
    experts without it."""
    names, values = model.functional_state()

    def router_inputs(vals, ids):
        caught = []
        hooks = [block.register_forward_pre_hook(
            lambda _layer, inputs: caught.append(inputs[0]))
            for block in blocks]
        try:
            with model.bind_state(names, list(vals)):
                run(ids)
        finally:
            for hook in hooks:
                hook.remove()
        return caught

    caught = jax.jit(router_inputs)(values, input_ids)
    for block, x in zip(blocks, caught):
        block.balance(x.reshape(-1, x.shape[-1]), rounds, step)


# A block of the sorted pair list is no fewer rows than a decode step
# has pairs (256 slots x 6): a decode step lays out every pair.
_MIN_BLOCK = 2048


def _pair_block(pairs, held, num_experts):
    """Rows of the sorted pair list a prefill lays out, from shapes
    alone: a third more than the held experts' share of the ``pairs``
    (their part of the router's width), so that a block holds the pairs
    of a layer whose load is near even; whole 128-row tiles; and, where
    ``moe_gmm`` would multiply every pair on 128-row tiles, enough rows
    a held expert that it does so on a block too."""
    block = max(_MIN_BLOCK, -(-4 * pairs * held // (3 * num_experts)))
    if pairs >= ROWS_A_GROUP * held:
        block = max(block, ROWS_A_GROUP * held)
    return -(-block // 128) * 128


def swiglu_clamped(gate, up, limit):
    """silu(min(gate, limit)) * clip(up, -limit, limit): a SwiGLU whose
    two halves are clamped (the ``swiglu_limit`` of a config)."""
    return (jax.nn.silu(jnp.minimum(gate, limit))
            * jnp.clip(up, -limit, limit))


def _run_experts(rows, row_key, sizes, w_in, b_in, w_out, b_out,
                 activation, gated, swiglu_limit=None):
    """The held experts on rows sorted by expert: ``rows`` [M, D],
    ``row_key`` [M] a row's held expert (``held`` for a row of no
    group), ``sizes`` [H] rows a group -> y [M, D]. A row of no group
    comes back undefined, not zero. ``swiglu_limit`` clamps a gated
    silu expert's two halves (``swiglu_clamped``)."""
    h = grouped_matmul(rows, w_in, sizes)
    # a row's own expert, for the biases (rows past every group: any)
    expert_of_row = jnp.minimum(row_key, w_in.shape[0] - 1)
    if b_in is not None:
        h = h + _A(b_in)[expert_of_row]
    act = _ACTIVATIONS[activation]
    if gated and swiglu_limit is not None:
        f = h.shape[-1] // 2
        h = swiglu_clamped(h[:, :f], h[:, f:], swiglu_limit)
    elif gated:
        f = h.shape[-1] // 2
        h = act(h[:, :f]) * h[:, f:]
    else:
        h = act(h)
    y = grouped_matmul(h.astype(rows.dtype), w_out, sizes)
    if b_out is not None:
        y = y + _A(b_out)[expert_of_row]
    return y


def _lay_out(x, weights, here, order, sorted_key, sizes, rows, top_k,
             experts):
    """The first ``rows`` rows of the sorted pair list laid out at once
    ([rows, ...] arrays), which must hold every pair of an expert held
    here: gather their rows of ``x``, run the experts, and sum each
    token's pairs back in float32. ``rows`` = T * k lays out every pair,
    whatever the routing: what a decode step runs, and a layer that
    holds every expert. -> out [T, D]."""
    t, d = x.shape
    pairs = order.shape[0]
    y = _run_experts(x[order[:rows] // top_k], sorted_key[:rows], sizes,
                     *experts)                                   # [rows, D]
    # back to pair order, one choice at a time: k gathers of [T, D]
    # summed in float32 as they come, so the [T, k, D] block is never
    # laid out (k is no multiple of a tile: that reshape is a copy).
    # A row of no group is undefined, not zero.
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(pairs, dtype=order.dtype)).reshape(t, top_k)
    if rows < pairs:
        # a pair held elsewhere sorts past the rows laid out: any row
        back = jnp.minimum(back, rows - 1)
    out = jnp.zeros((t, d), jnp.float32)
    for j in range(top_k):
        picked = y[back[:, j]].astype(jnp.float32) * weights[:, j, None]
        out = out + jnp.where(here[:, j, None], picked, 0.0)
    return out.astype(x.dtype)


def moe_forward(x, gate_w, w_in, b_in, w_out, b_out, *, top_k, lo=0,
                activation="gelu", gated=False, norm_topk_prob=None,
                n_group=1, topk_group=1, routed_scaling_factor=1.0,
                select_bias=None, swiglu_limit=None, row_mask=None):
    """The dropless expert layer on raw arrays.

    x [T, D]; gate_w [D, E] routes over all E published experts; the
    held experts are ``lo .. lo + H - 1`` with w_in [H, D, F] (or
    [H, D, 2F] when ``gated``: gate and up side by side, out =
    act(gate) * up), w_out [H, F, D]; b_in [H, F or 2F] / b_out [H, D]
    or None. ``norm_topk_prob`` None means "when top_k > 1" (a single
    choice keeps its raw probability, or the router would get no
    gradient); ``n_group``, ``topk_group``, ``routed_scaling_factor``
    and ``select_bias`` are ``route``'s; ``swiglu_limit`` clamps a gated
    silu expert (``swiglu_clamped``); ``row_mask`` (bool [T]) names the
    rows whose pairs are computed at all: a row outside it (a prefill's
    padding) is routed, its pairs are counted as held elsewhere and its
    routed share is 0. -> (out [T, D], aux loss, stats
    int32 [4]): the share of the result the held experts give; pairs
    routed here, held experts that received a row, the largest load of
    one expert, rows of the sorted pair list handed to the grouped
    matmuls.

    Where some experts are held elsewhere and the T * k pairs are more
    than one block (``_pair_block``: a prefill), the layer lays out the
    first block of the sorted list if every pair that lands here is in
    it, and every pair if not; else (a decode step, a layer that holds
    every expert) every pair, as one program."""
    x = _A(x)
    w_in, w_out = _A(w_in), _A(w_out)
    t, d = x.shape
    held = w_in.shape[0]
    if norm_topk_prob is None:
        norm_topk_prob = top_k > 1
    weights, experts, aux = route(x, gate_w, top_k, norm_topk_prob,
                                  n_group, topk_group,
                                  routed_scaling_factor, select_bias)
    here = jnp.logical_and(experts >= lo, experts < lo + held)   # [T, k]
    if row_mask is not None:
        here = jnp.logical_and(here, row_mask[:, None])
    # pairs sorted by held expert; the pairs of experts held elsewhere
    # sort to the end, past every group: the live count is ends[held]
    key = jnp.where(here, experts - lo, held).reshape(-1)        # [T*k]
    order = jnp.argsort(key, stable=True)
    sorted_key = key[order]
    # the keys are sorted: a group's rows lie between two searches
    ends = jnp.searchsorted(sorted_key, jnp.arange(held + 1, dtype=key.dtype))
    sizes = (ends[1:] - ends[:-1]).astype(jnp.int32)
    lay_out = functools.partial(
        _lay_out, top_k=top_k,
        experts=(w_in, b_in, w_out, b_out, activation, gated,
                 swiglu_limit))
    routed = (x, weights, here, order, sorted_key, sizes)
    pairs, num_experts = t * top_k, jnp.shape(gate_w)[-1]
    block = _pair_block(pairs, held, num_experts)
    if held < num_experts and pairs > block:
        # two programs under one cond: a block where it holds every
        # pair that lands here, which it is sized to do; every pair
        # where the routing is skewed past it, so none is dropped
        rows_run = jnp.where(ends[held] <= block, block, pairs)
        out = jax.lax.cond(rows_run == block,
                           functools.partial(lay_out, rows=block),
                           functools.partial(lay_out, rows=pairs), *routed)
    else:
        rows_run = pairs
        out = lay_out(*routed, rows=pairs)
    stats = jnp.stack([jnp.sum(here, dtype=jnp.int32),
                       jnp.sum(sizes > 0, dtype=jnp.int32),
                       jnp.max(sizes),
                       jnp.asarray(rows_run, jnp.int32)])
    return out, aux.astype(x.dtype), stats


@primitive
def moe_mlp(x, gate_w, w1, b1, w2, b2, *, top_k, lo=0, activation="gelu",
            gated=False, norm_topk_prob=None):
    """``moe_forward`` as an eager op: (out [T, D], aux_loss scalar)."""
    out, aux, _ = moe_forward(
        x, gate_w, w1, b1, w2, b2, top_k=top_k, lo=lo,
        activation=activation, gated=gated, norm_topk_prob=norm_topk_prob)
    return out, aux


class MoELayer(Layer):
    """MoE feed-forward block (reference moe_layer.py:260 MoELayer).

    ``num_experts`` is the router's width; ``experts_held`` (a range,
    default all of them) says which of them live here, and only those
    have weights: stacked [held, ...] parameters, not a list of Expert
    sublayers. ``gate="switch"`` is top-1, as in the reference's gates.
    ``gated`` makes each expert a SwiGLU-style pair (w1 holds gate and
    up side by side); ``bias=False`` drops b1 / b2.

    After forward, ``self.aux_loss`` holds the load-balancing loss
    tensor: add ``moe.aux_loss * coeff`` to the training loss (the
    reference returns it through its gate object the same way).
    """

    def __init__(self, d_model, d_hidden, num_experts, top_k=2,
                 gate="gshard", activation="gelu", gated=False, bias=True,
                 norm_topk_prob=None, experts_held=None, dtype=None,
                 name=None):
        super().__init__()
        if gate == "switch":
            top_k = 1
        held = range(num_experts) if experts_held is None else experts_held
        if held.step != 1 or not 0 <= held.start < held.stop <= num_experts:
            raise ValueError("experts_held must be a contiguous range "
                             "inside range(%d), got %r"
                             % (num_experts, held))
        self.d_model = d_model
        self.d_hidden = d_hidden
        self.num_experts = num_experts
        self.experts_held = held
        self.top_k = top_k
        self.activation = activation
        self.gated = gated
        self.norm_topk_prob = norm_topk_prob
        n, wide = len(held), d_hidden * (2 if gated else 1)
        init = I.XavierNormal()
        self.gate_weight = self.create_parameter(
            [d_model, num_experts], dtype=dtype, default_initializer=init)
        self.w1 = self.create_parameter(
            [n, d_model, wide], dtype=dtype, default_initializer=init)
        self.w2 = self.create_parameter(
            [n, d_hidden, d_model], dtype=dtype, default_initializer=init)
        self.b1 = self.b2 = None
        if bias:
            self.b1 = self.create_parameter([n, wide], dtype=dtype,
                                            is_bias=True)
            self.b2 = self.create_parameter([n, d_model], dtype=dtype,
                                            is_bias=True)
        self.aux_loss = None

    def forward(self, x):
        shape = x.shape
        x2 = x.reshape([-1, shape[-1]])
        out, aux = moe_mlp(
            x2, self.gate_weight, self.w1, self.b1, self.w2, self.b2,
            top_k=self.top_k, lo=self.experts_held.start,
            activation=self.activation, gated=self.gated,
            norm_topk_prob=self.norm_topk_prob)
        self.aux_loss = aux
        return out.reshape(shape)


# ---------------------------------------------------------------------------
# Eager all-to-all primitives for API parity with the reference's
# global_scatter/global_gather (operators/collective/global_scatter_op.cc).
# TPU deviation: XLA all-to-all moves equal-size splits; the reference's
# variable-count protocol (exchange counts, then ragged payloads) has no
# static-shape analog, so equal splits are required. The expert layer
# above does not call these: it computes its own experts' share and
# leaves the exchange between chips to a later PR (ROADMAP R3).
# ---------------------------------------------------------------------------

def global_scatter(x, group=None):
    """Exchange locally-grouped expert rows so each rank holds the rows of
    its own experts from every peer. x: [E * C, ...] with the leading dim
    grouped by (global) expert; requires E divisible by the group size."""
    from ..distributed import collective

    return collective.alltoall(x, group=group)


def global_gather(x, group=None):
    """Inverse of global_scatter (the same equal-split all_to_all with the
    send/receive roles swapped)."""
    from ..distributed import collective

    return collective.alltoall(x, group=group)
