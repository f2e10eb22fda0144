"""Token sampling: greedy / temperature / top-k / top-p, vmappable and
jit-stable (no data-dependent shapes — masks, not gathers)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30

# the three paths of sample_logits, cheapest first: the names the engine's
# dispatch spans and app_sampler_steps_total carry
SAMPLER_PATHS = ("greedy", "sample", "filter")


def sampler_path(temperature, top_k, top_p, rows=None):
    """Which path of :func:`sample_logits` a batch takes, as an index into
    ``SAMPLER_PATHS`` (an int32 scalar): 0 when no row that counts samples,
    1 when some do and none asks for top-k or top-p, 2 otherwise.
    ``top_k <= 0`` and ``top_p >= 1`` mean "off". Takes device arrays (the
    predicate the program branches on) or the host's numpy mirrors of them
    (the engine's spans) alike: one definition for both."""
    samples = temperature > 0
    if rows is not None:
        samples = rows & samples
    filters = samples & ((top_k > 0) | (top_p < 1.0))
    return samples.any().astype("int32") + filters.any().astype("int32")


def sample_logits(
    logits: jnp.ndarray,  # [B, vocab]
    key: jax.Array,
    *,
    temperature: jnp.ndarray | float = 1.0,
    top_k: jnp.ndarray | int = 0,  # 0 = disabled
    top_p: jnp.ndarray | float = 1.0,
    rows: jnp.ndarray | None = None,  # [B] bool — the rows whose token counts
) -> jnp.ndarray:
    """Returns sampled token ids [B]. temperature==0 → greedy (exact argmax,
    not a divide-by-zero). Per-request scalars may be arrays broadcast over
    the batch for continuous batching (each row has its own params).

    The work follows what the rows that count ask for (``rows``; default:
    every row), decided on the device by :func:`sampler_path`: if none of
    them samples, the argmax alone; if some sample and none sets top-k or
    top-p, ``jax.random.categorical`` over the scaled logits; else the
    whole pipeline with its two sorts of the vocabulary. Greedy rows get
    the same argmax on every path, and a batch on the whole pipeline gets
    that pipeline's tokens row by row. The middle path samples the stated
    distribution, where the pipeline at ``top_p = 1.0`` could drop tail
    tokens once its float32 cumulative sum rounded past 1. Under
    ``jax.vmap`` the branches become selects and all of them run: hoist
    the predicate out of the ``vmap`` instead (batch._fold_finished_prefill)."""
    logits = logits.astype(jnp.float32)
    temperature = jnp.asarray(temperature, dtype=jnp.float32)
    top_k = jnp.asarray(top_k, dtype=jnp.int32)
    top_p = jnp.asarray(top_p, dtype=jnp.float32)

    greedy_ids = jnp.argmax(logits, axis=-1)

    def scaled_logits():
        safe_temp = jnp.where(temperature > 0, temperature, 1.0)
        return logits / _expand(safe_temp, logits)

    def pick(sampled):
        take_greedy = jnp.broadcast_to(temperature <= 0, sampled.shape)
        return jnp.where(take_greedy, greedy_ids, sampled)

    def greedy():
        return greedy_ids

    def sample():
        return pick(jax.random.categorical(key, scaled_logits(), axis=-1))

    def filtered():
        scaled = scaled_logits()
        # top-k mask: keep logits >= k-th largest (static vocab shape)
        vocab = logits.shape[-1]
        sorted_desc = jnp.sort(scaled, axis=-1)[..., ::-1]
        k_idx = jnp.clip(jnp.where(top_k > 0, top_k, vocab) - 1, 0, vocab - 1)
        kth = jnp.take_along_axis(sorted_desc, _expand(k_idx, logits).astype(jnp.int32), axis=-1)
        scaled = jnp.where(scaled >= kth, scaled, NEG_INF)

        # top-p (nucleus): drop tokens beyond cumulative prob p in sorted order
        sorted_scaled = jnp.sort(scaled, axis=-1)[..., ::-1]
        probs_sorted = jax.nn.softmax(sorted_scaled, axis=-1)
        cum = jnp.cumsum(probs_sorted, axis=-1)
        # keep the first token whose cumulative prob crosses p (always >=1 kept)
        cutoff_mask = cum - probs_sorted < _expand(top_p, logits)
        threshold = jnp.min(
            jnp.where(cutoff_mask, sorted_scaled, jnp.inf), axis=-1, keepdims=True
        )
        scaled = jnp.where(scaled >= threshold, scaled, NEG_INF)
        return pick(jax.random.categorical(key, scaled, axis=-1))

    return jax.lax.switch(
        sampler_path(temperature, top_k, top_p, rows), (greedy, sample, filtered)
    )


def _expand(x: jnp.ndarray, ref: jnp.ndarray) -> jnp.ndarray:
    """Broadcast a scalar or [B] array to [B, 1] against ref [B, vocab]."""
    x = jnp.asarray(x)
    if x.ndim == 0:
        return x[None, None]
    return x[:, None]


def stop_eval(
    next_token: jnp.ndarray,  # [B] the token each row just emitted
    stop_tok: jnp.ndarray,  # [B] per-row stop (EOS) id; -1 disables
    budget: jnp.ndarray,  # [B] tokens the row may still emit, INCLUDING this one
) -> jnp.ndarray:
    """On-device stop-condition evaluation (the other half of the fused
    decode step — Blink's CPU-free loop, arXiv:2604.07609): a row is done
    when the token it just emitted is its stop token, or when that token
    spent the last of its budget (``max_new_tokens`` and the sequence-length
    cap are both folded into ``budget`` by the engine at admission). Keeping
    this on device is what lets the host read back once per N-step block
    instead of scanning every token for EOS. Returns done [B] bool."""
    return (next_token == stop_tok) | (budget <= 1)
