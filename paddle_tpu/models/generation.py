"""Shared autoregressive generation machinery.

Reference analog: PaddleNLP GenerationMixin (greedy/sampling over growing
DenseTensor caches, top_k_top_p sampling ops). TPU-first shape instead:

- `DecodeCache`: static-size per-layer KV buffer (pytree NamedTuple) —
  written with dynamic_update_slice at the position head, ONE compiled
  shape for the whole generation (growing caches would recompile every
  step under XLA).
- `GenerationMixin.generate`: jitted prefill over the prompt (flash
  kernel eligible), then the entire decode loop as a single XLA
  while-loop with eos early-exit.

A model opts in by providing:
  generate_step(input_ids, caches, position_offset, logits_at=None)
      -> (logits, caches); a caller that reads one row a sequence names
      it in `logits_at` (int32 [B]) and gets logits [B, 1, vocab]
  init_decode_caches(batch, total_len) -> list[DecodeCache]
  functional_state() / bind_state(...)  (nn.Layer already has these)
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp


class DecodeCache(NamedTuple):
    """[B, L_max, H_kv, D] static KV buffers for one layer."""

    k: "object"
    v: "object"


def cache_update(cache, k, v, position_offset):
    """Write s new K/V rows into the static buffers at position_offset;
    returns (new_cache, k_full, v_full) with k/v as full-buffer Tensors."""
    import jax

    from ..core.tensor import Tensor

    def _upd(buf, new):
        nv = new._value if hasattr(new, "_value") else jnp.asarray(new)
        return jax.lax.dynamic_update_slice(
            buf, nv.astype(buf.dtype), (0, position_offset, 0, 0))

    kb = _upd(cache.k, k)
    vb = _upd(cache.v, v)
    return DecodeCache(kb, vb), Tensor(kb), Tensor(vb)


def decode_mask(position_offset, s, kv_len):
    """Valid-region causal mask for cached decode, or the string "causal"
    when it reduces to plain start-aligned causality (static prefill at
    offset 0 — lets the flash kernel stay eligible)."""
    if isinstance(position_offset, int) and position_offset == 0:
        return "causal"
    kv_pos = jnp.arange(kv_len)
    q_pos = position_offset + jnp.arange(s)
    return kv_pos[None, :] <= q_pos[:, None]  # [s, kv]


def masked_decode_attention(q, k, v, mask):
    """Dispatch on decode_mask()'s result."""
    from ..nn import functional as F

    if isinstance(mask, str):  # "causal"
        # prefill at offset 0 against a preallocated cache: start-aligned
        # is exactly right (uninitialized tail slots are masked)
        return F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                              _warn_rect_causal=False)
    return F.scaled_dot_product_attention(
        q, k, v, attn_mask=mask[None, None], is_causal=False)


def rows_at(hidden, logits_at):
    """The rows of ``hidden`` [B, T, H] that the caller will read:
    ``logits_at`` is int32 [B] (may be traced), one row index a
    sequence, and the result is [B, 1, H]; ``None`` reads every row.
    A model's ``generate_step`` calls this BEFORE its final norm and
    head, so that a prefill over a padded bucket computes them for the
    one row whose token it takes, not for all T."""
    from ..core.tensor import Tensor

    if logits_at is None:
        return hidden
    hv = hidden._value if isinstance(hidden, Tensor) else hidden
    idx = jnp.asarray(logits_at, jnp.int32).reshape(-1, 1, 1)
    out = jnp.take_along_axis(hv, idx, axis=1)
    return Tensor(out) if isinstance(hidden, Tensor) else out


class GenerationMixin:
    def max_decode_len(self):
        """Maximum total sequence length (prompt + generated), or None
        when unbounded. Models override."""
        return None

    def _coerce_prompt(self, input_ids, max_new_tokens):
        """-> (ids int32 [b, prompt_len], b, prompt_len, total); validates
        against max_decode_len (out-of-range positions would clamp in
        XLA's gather for learned position tables, or extrapolate silently
        for rope)."""
        from ..core.tensor import Tensor

        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        ids = ids.astype(jnp.int32)
        b, prompt_len = ids.shape
        total = prompt_len + max_new_tokens
        limit = self.max_decode_len()
        if limit is not None and total > limit:
            raise ValueError(
                "generate: prompt_len (%d) + max_new_tokens (%d) exceeds "
                "the model's maximum sequence length (%d)"
                % (prompt_len, max_new_tokens, limit))
        return ids, b, prompt_len, total

    def _jit_cached(self, cache_key, build, state_names=()):
        """Per-signature compiled-callable cache, bounded at 16 retained
        executables (varying prompt lengths in a serving loop would
        otherwise grow it forever). The functional-state NAMES are part
        of the key: a compiled program binds state positionally against
        the name list it was traced with, so any module-tree mutation
        (e.g. quantization.convert_to_int8 swapping Linear->Int8Linear,
        possibly on a deep copy that inherited this cache) must miss the
        cache instead of mis-binding the new value list."""
        import jax

        cache_key = cache_key + (tuple(state_names),)
        jit_cache = self.__dict__.setdefault("_generate_jit_cache", {})
        compiled = jit_cache.get(cache_key)
        if compiled is None:
            if len(jit_cache) >= 16:
                jit_cache.pop(next(iter(jit_cache)))
            compiled = jax.jit(build())
            jit_cache[cache_key] = compiled
        return compiled

    def _make_step_logits(self, names, state_vals, as_f32=False):
        """One decode step shared by every strategy: bind functional
        state, run generate_step, return last-token logits + caches."""
        from ..core.dispatch import no_grad
        from ..core.tensor import Tensor

        def step_logits(token_ids, caches, offset):
            with self.bind_state(names, list(state_vals)):
                with no_grad():
                    logits, caches = self.generate_step(
                        Tensor(token_ids), caches, offset)
            lv = logits._value if isinstance(logits, Tensor) else logits
            lv = lv[:, -1, :]
            return (lv.astype(jnp.float32) if as_f32 else lv), caches

        return step_logits

    def _run_eval(self, compiled, *args):
        """Invoke a compiled generation program in inference semantics:
        dropout off inside the traced loop (Layer.training defaults True;
        a traced train-mode dropout would corrupt logits with one frozen
        mask per trace), training flag restored after."""
        from ..core.dispatch import no_grad
        from ..core.tensor import Tensor

        was_training = self.training
        self.eval()
        try:
            with no_grad():
                out = compiled(*args)
        finally:
            if was_training:
                self.train()
        return Tensor(out)

    def generate(self, input_ids, max_new_tokens=32, do_sample=False,
                 top_k=0, top_p=1.0, temperature=1.0, eos_token_id=None,
                 seed=0, num_beams=1, length_penalty=0.0):
        """Autoregressive generation, compiled end to end. Returns the
        generated ids [B, max_new_tokens] (prompt excluded); positions
        after a sequence's eos are padded with eos.

        num_beams > 1 switches to beam search (reference PaddleNLP
        decode_strategy='beam_search'): beams live as an expanded batch
        inside the same compiled while-loop; each step takes the top
        num_beams continuations over (beams x vocab) cumulative
        log-probs, with finished beams frozen on eos. length_penalty is
        the GNMT exponent alpha (score / len^alpha) applied at the final
        beam selection."""
        import jax

        from ..core.dispatch import no_grad
        from ..core.tensor import Tensor

        if num_beams > 1:
            if do_sample:
                raise ValueError(
                    "beam search is deterministic; do_sample=True "
                    "conflicts with num_beams > 1")
            return self._beam_search(input_ids, max_new_tokens, num_beams,
                                     eos_token_id, length_penalty,
                                     temperature)

        ids, b, prompt_len, total = self._coerce_prompt(
            input_ids, max_new_tokens)
        names, values = self.functional_state()

        def sample(logits, key):
            logits = logits.astype(jnp.float32) / max(temperature, 1e-6)
            if not do_sample:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            if top_k:
                kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
                logits = jnp.where(logits < kth, -jnp.inf, logits)
            if top_p < 1.0:
                sorted_l = jnp.sort(logits, axis=-1)[:, ::-1]
                probs = jax.nn.softmax(sorted_l, axis=-1)
                cum = jnp.cumsum(probs, axis=-1)
                # smallest prefix with mass >= top_p stays
                cutoff_idx = jnp.sum(cum < top_p, axis=-1)
                cutoff = jnp.take_along_axis(
                    sorted_l, cutoff_idx[:, None], axis=-1)
                logits = jnp.where(logits < cutoff, -jnp.inf, logits)
            return jax.random.categorical(key, logits, axis=-1) \
                .astype(jnp.int32)

        def run(state_vals, ids, key):
            caches = self.init_decode_caches(b, total)
            step_logits = self._make_step_logits(names, state_vals)

            # prefill the whole prompt in one pass
            last, caches = step_logits(ids, caches, 0)
            key, sub = jax.random.split(key)
            tok = sample(last, sub)
            fill = eos_token_id if eos_token_id is not None else 0
            out0 = jnp.full((b, max_new_tokens), fill, jnp.int32) \
                .at[:, 0].set(tok)
            done0 = (tok == eos_token_id) if eos_token_id is not None \
                else jnp.zeros((b,), bool)

            def cond(carry):
                i, tok, caches, out, done, key = carry
                return jnp.logical_and(i < max_new_tokens,
                                       jnp.logical_not(jnp.all(done)))

            def body(carry):
                i, tok, caches, out, done, key = carry
                last, caches = step_logits(tok[:, None], caches,
                                           prompt_len + i - 1)
                key, sub = jax.random.split(key)
                nxt = sample(last, sub)
                if eos_token_id is not None:
                    nxt = jnp.where(done, eos_token_id, nxt)
                    done = jnp.logical_or(done, nxt == eos_token_id)
                out = out.at[:, i].set(nxt)
                return (i + 1, nxt, caches, out, done, key)

            # decode loop: one XLA while_loop (early exit on all-eos)
            _, _, _, out, _, _ = jax.lax.while_loop(
                cond, body, (1, tok, caches, out0, done0, key))
            return out

        compiled = self._jit_cached(
            (b, prompt_len, max_new_tokens, do_sample, top_k, top_p,
             temperature, eos_token_id), lambda: run,
            state_names=names)
        return self._run_eval(compiled, list(values), ids,
                              jax.random.key(seed))

    def _beam_search(self, input_ids, max_new_tokens, num_beams,
                     eos_token_id, length_penalty, temperature):
        import jax

        from ..core.dispatch import no_grad
        from ..core.tensor import Tensor

        ids, b, prompt_len, total = self._coerce_prompt(
            input_ids, max_new_tokens)
        names, values = self.functional_state()
        K = num_beams
        NEG = jnp.float32(-1e9)

        def run(state_vals, ids):
            step_logits = self._make_step_logits(names, state_vals,
                                                 as_f32=True)

            # prefill ONCE at batch b (beams are byte-identical over the
            # prompt), then fan the caches/logits out to b*K beam rows
            caches = self.init_decode_caches(b, total)
            last, caches = step_logits(ids, caches, 0)
            caches = jax.tree_util.tree_map(
                lambda x: jnp.repeat(x, K, axis=0), caches)
            last = jnp.repeat(last, K, axis=0)           # [b*K, V]
            logp = jax.nn.log_softmax(last / max(temperature, 1e-6), -1)
            vocab = logp.shape[-1]
            # first step: all beams of a batch row are identical — mask
            # beams 1..K-1 so the top-K picks K DISTINCT first tokens
            beam_mask = jnp.where(
                jnp.arange(b * K) % K == 0, 0.0, NEG)[:, None]
            scores0 = (logp + beam_mask).reshape(b, K * vocab)
            top_s, top_i = jax.lax.top_k(scores0, K)     # [b, K]
            tok0 = (top_i % vocab).astype(jnp.int32)
            out0 = jnp.full((b, K, max_new_tokens),
                            eos_token_id if eos_token_id is not None else 0,
                            jnp.int32).at[:, :, 0].set(tok0)
            done0 = ((tok0 == eos_token_id) if eos_token_id is not None
                     else jnp.zeros((b, K), bool))
            # NOTE: beams share the prefill cache rows (identical prompt),
            # so no cache reorder is needed at the first step
            carry0 = (jnp.asarray(1), tok0, caches, out0, top_s, done0)

            def cond(c):
                i, tok, caches, out, scores, done = c
                return jnp.logical_and(i < max_new_tokens,
                                       jnp.logical_not(jnp.all(done)))

            def body(c):
                i, tok, caches, out, scores, done = c
                last, caches = step_logits(
                    tok.reshape(b * K, 1), caches, prompt_len + i - 1)
                logp = jax.nn.log_softmax(
                    last / max(temperature, 1e-6), -1)   # [b*K, V]
                logp = logp.reshape(b, K, vocab)
                if eos_token_id is not None:
                    # finished beams: only eos continues, at zero cost
                    frozen = jnp.full((vocab,), NEG).at[eos_token_id].set(0.0)
                    logp = jnp.where(done[:, :, None], frozen[None, None, :],
                                     logp)
                cand = (scores[:, :, None] + logp).reshape(b, K * vocab)
                scores, idx = jax.lax.top_k(cand, K)     # [b, K]
                src_beam = idx // vocab                  # [b, K]
                nxt = (idx % vocab).astype(jnp.int32)
                # reorder carried state to the winning source beams
                flat_src = (jnp.arange(b)[:, None] * K + src_beam) \
                    .reshape(-1)                         # [b*K]
                caches = jax.tree_util.tree_map(
                    lambda x: x[flat_src], caches)
                out = jnp.take_along_axis(
                    out, src_beam[:, :, None], axis=1)
                done = jnp.take_along_axis(done, src_beam, axis=1)
                if eos_token_id is not None:
                    done = jnp.logical_or(done, nxt == eos_token_id)
                out = out.at[:, :, i].set(nxt)
                return (i + 1, nxt, caches, out, scores, done)

            i, _, _, out, scores, done = jax.lax.while_loop(
                cond, body, carry0)
            # GNMT length normalization at final selection
            if length_penalty:
                lengths = jnp.where(
                    done,
                    jnp.argmax(
                        out == (eos_token_id
                                if eos_token_id is not None else -1),
                        axis=-1) + 1,
                    i).astype(jnp.float32).clip(min=1.0)
                norm = scores / (lengths ** length_penalty)
            else:
                norm = scores
            best = jnp.argmax(norm, axis=1)              # [b]
            return jnp.take_along_axis(
                out, best[:, None, None], axis=1)[:, 0]

        compiled = self._jit_cached(
            ("beam", b, prompt_len, max_new_tokens, K, eos_token_id,
             length_penalty, temperature), lambda: run,
            state_names=names)
        return self._run_eval(compiled, list(values), ids)
