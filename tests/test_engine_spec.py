"""Speculative decoding inside the ServingEngine (VERDICT r4 item #3).

Prompt-lookup drafting + batched chunk-verify through the KV page pool.
The contract is LOSSLESSNESS: with temperature
0 the spec engine's output equals the plain engine's token for token —
acceptance is exact argmax equality, so drafts only change how many
dispatches the tokens take, never which tokens come out. Library-level
twin: models/llama.py speculative_generate (tests/test_speculative.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.models import llama
from gofr_tpu.serving import ByteTokenizer, EngineConfig, ServingEngine

CFG = llama.LlamaConfig(
    vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
    d_ff=64, max_seq_len=128,
)
PARAMS = llama.init_params(CFG, jax.random.PRNGKey(0))

# byte prompts repeat, so prompt-lookup finds continuations to draft
REPETITIVE = "abcd abcd abcd abcd abcd"


def run_engine(spec_tokens: int, prompt: str,
               max_new: int, temperature: float = 0.0, seed: int = 0):
    eng = ServingEngine(
        CFG, PARAMS,
        EngineConfig(
            max_slots=2, max_seq_len=128, prefill_buckets=(32,),
            kv_page_size=8, spec_tokens=spec_tokens,
        ),
        ByteTokenizer(CFG.vocab_size),
        seed=seed,
    )
    eng.start()
    try:
        res = eng.submit(
            prompt, max_new_tokens=max_new, temperature=temperature
        ).result(timeout=300)
        return res, dict(eng.spec_stats)
    finally:
        eng.stop()


def test_spec_token_equality_vs_plain_engine():
    base, _ = run_engine(0, REPETITIVE, 24)
    spec, stats = run_engine(6, REPETITIVE, 24)
    assert spec.token_ids == base.token_ids
    assert spec.finish_reason == base.finish_reason
    # repetition-heavy greedy decoding must beat one token per dispatch —
    # the whole point of drafting (CPU proxy for the TPU tok/s uplift)
    assert stats["emitted"] > stats["dispatches"]
    assert stats["accepted"] > 0


def test_spec_sampled_rows_take_plain_steps():
    """temperature > 0 rows are not drafted for (greedy verification
    would bias sampling); they still decode correctly through the chunk
    executable, taking PLAIN single-token steps (one committed token per
    verify dispatch) under the packed-step contract.

    Prefill first-token sampling is keyed fold_in(PRNGKey(seed), rid) —
    independent of admission/decode interleave (the in-suite flake fix,
    engine._rng_root) — and at seed 0 / rid 1 the draw is NOT EOS, so
    the row reaches its spec steps."""
    res, stats = run_engine(6, REPETITIVE, 12, temperature=0.8, seed=0)
    assert res.completion_tokens == len(res.token_ids)
    assert res.completion_tokens >= 1
    assert stats["accepted"] == 0  # no drafts for sampled rows
    # plain steps: every verify dispatch commits exactly one token
    assert stats["emitted"] == stats["dispatches"]


def test_spec_concurrent_mixed_requests():
    """Greedy and sampled rows share chunks; slot churn under spec mode
    stays correct (stop/length mid-chunk discards the tail)."""
    eng = ServingEngine(
        CFG, PARAMS,
        EngineConfig(
            max_slots=4, max_seq_len=128, prefill_buckets=(32,),
            spec_tokens=4,
        ),
        ByteTokenizer(CFG.vocab_size),
    )
    eng.start()
    try:
        futs = [
            eng.submit(REPETITIVE, max_new_tokens=(5, 9, 17)[i % 3],
                       temperature=0.0 if i % 2 == 0 else 0.7)
            for i in range(9)
        ]
        for i, f in enumerate(futs):
            res = f.result(timeout=300)
            want = (5, 9, 17)[i % 3]
            assert res.finish_reason in ("stop", "length")
            assert 1 <= res.completion_tokens <= want
    finally:
        eng.stop()


def test_spec_paged_token_equality_vs_reference():
    """The spec path over the page pool decodes the greedy tokens of the
    model's plain path (llama.greedy_generate over a KVCache) for the
    same prompt, up to the reference's first EOS."""
    tok = ByteTokenizer(CFG.vocab_size)
    ids = tok.encode(REPETITIVE)
    ref = llama.greedy_generate(
        CFG, PARAMS, jnp.asarray([ids], jnp.int32), jnp.asarray([len(ids)]), 20
    )
    expect = []
    for t in np.asarray(ref[0]).tolist():
        if t == tok.eos_id:
            break
        expect.append(t)
    paged, _ = run_engine(6, REPETITIVE, 20)
    assert paged.token_ids == expect


def test_spec_config_validation():
    with pytest.raises(ValueError, match="chunking"):
        ServingEngine(
            CFG, PARAMS,
            EngineConfig(max_slots=2, max_seq_len=64, spec_tokens=4,
                         multi_step=4),
            ByteTokenizer(CFG.vocab_size),
        )


def test_spec_paged_request_runs_to_sequence_limit():
    """A row that decodes all the way to max_seq_len must not overflow the
    per-sequence block-table width when the spec chunk reserves past the
    end (code-review r5): the reservation clamps to max_seq_len and chunk
    tail positions divert to the trash page."""
    small = llama.LlamaConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=48,
    )
    params = llama.init_params(small, jax.random.PRNGKey(1))
    eng = ServingEngine(
        small, params,
        EngineConfig(
            max_slots=2, max_seq_len=48, prefill_buckets=(16,),
            kv_page_size=8, spec_tokens=6,
        ),
        ByteTokenizer(small.vocab_size),
    )
    eng.start()
    try:
        # prompt 16 tokens (bucket) + max_new up to the sequence budget:
        # the row rides to max_seq-1 and the final chunks straddle the end
        res = eng.submit(
            REPETITIVE[:16], max_new_tokens=100, temperature=0.0
        ).result(timeout=300)
        assert res.finish_reason in ("stop", "length")
        # the sequence really hit the cap (unless a stop token cut it)
        if res.finish_reason == "length":
            assert res.prompt_tokens + res.completion_tokens >= 47
    finally:
        eng.stop()
