"""``models/phi4flash.py`` against its plain reference
(``benchmarks/harness/phi4flash_reference.py``: float32, every layer over
every position, two softmaxes a pair on K and V as published, nothing
imported from the program) at tiny widths on the CPU: 8 layers (two
state-space/window pairs, the middle, one cross pair), hidden 64, 4 / 2
heads of 16, ``d_state`` 4, window 8, pages of 4 — seeded weights.

Tolerances, each with its reason:

- ``TOL`` 2e-3 on logits of deviation about 1: the program in float32
  differs from the reference by the order of sums only (readings 1e-5 to
  4e-5 through prefill, chunks and 300 decode steps); the int8 tree by the
  same (both de-quantise the same integers). The int4 control reads 0.5 to 2
  and a bfloat16 recurrent state 5e-3 to 5e-2 here: both fail ``TOL``, each by
  more than twice.
"""

import json
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import phi4flash_reference as reference
from gofr_tpu.models import cohere2_moe, deepseek_v32, llama
from gofr_tpu.models import phi4flash as phi
from gofr_tpu.ops import ssm
from gofr_tpu.ops.attention import attention
from gofr_tpu.ops.paged_attention import paged_decode_attention
from gofr_tpu.serving import ByteTokenizer, EngineConfig, ServingEngine
from gofr_tpu.serving import batch as batch_ops
from gofr_tpu.serving.kv_cache import PagedKVCache

CFG = phi.Phi4FlashConfig.tiny(vocab_size=300)
PAGE, TOL = 4, 2e-3


def as_file(cfg):
    """The program's config under the published keys the reference reads."""
    return {"hidden_size": cfg.d_model, "num_hidden_layers": cfg.n_layers, "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim, "intermediate_size": cfg.d_ff,
            "sliding_window": cfg.sliding_window, "layer_norm_eps": cfg.norm_eps, "mb_per_layer": 2,
            "vocab_size": cfg.vocab_size}


@pytest.fixture(scope="module")
def plain():
    return phi.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def int8(plain):
    return phi.quantize_params(plain)


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(7).integers(0, CFG.vocab_size, 360).astype(np.int32)


def pager(cfg=CFG, slots=2, max_seq=384):
    return PagedKVCache(cfg, num_pages=slots * max_seq // PAGE, page_size=PAGE, max_slots=slots,
                        max_seq_len=max_seq, spec=phi.cache_spec(cfg, PAGE))


def bucketed(cfg, params, pc, slot, prompt, bucket, seq_id=1):
    """Prefill ``prompt`` in a bucket and write it into ``slot``; the last position's logits."""
    tokens = np.zeros((1, bucket), np.int32)
    tokens[0, :len(prompt)] = prompt
    last, cache = phi.prefill(cfg, params, jnp.asarray(tokens), phi.KVCache.create(cfg, 1, bucket),
                              jnp.asarray([len(prompt)]))
    pc.alloc_slot(slot, seq_id=seq_id, prompt_len=len(prompt), reserve_tokens=bucket)
    pc.write_prefill(slot, *phi.prefill_slabs(cache))
    return np.asarray(last[0])


def chunked(cfg, params, pc, slot, prompt, chunk, seq_id=1):
    """Prefill ``prompt`` through chunks into ``slot``; the logits each chunk returned for the row."""
    B, out, start = pc.max_slots, [], 0
    pc.alloc_slot(slot, seq_id=seq_id, prompt_len=0, reserve_tokens=min(chunk, len(prompt)))
    while start < len(prompt):
        n = min(chunk, len(prompt) - start)
        if start:
            assert pc.try_reserve_slot(slot, n)
        toks = np.full((B, chunk), -1, np.int32)
        toks[slot, :n] = prompt[start:start + n]
        starts = np.full(B, pc.max_seq_len, np.int32)
        starts[slot] = start
        active, cap, finish = np.zeros(B, bool), np.zeros(B, np.int32), np.zeros(B, bool)
        active[slot], cap[slot], finish[slot] = True, pc.owned_capacity(slot), start + n >= len(prompt)
        logits, pc.k_pool, pc.v_pool = phi.decode_chunk_paged(
            cfg, params, jnp.asarray(toks), pc.k_pool, pc.v_pool, pc.tables_device(), jnp.asarray(starts),
            jnp.asarray(active), jnp.asarray(cap), jnp.asarray(finish))
        pc.advance_slot(slot, n)
        start += n
        out.append(np.asarray(logits[slot, 0]))
    return out


def decode(cfg, params, pc, slot, tokens, each=None):
    """Feed ``tokens`` one step at a time to ``slot`` (the other slots idle); logits [len, V]."""
    B, out = pc.max_slots, []
    for t in tokens:
        assert pc.try_reserve_slot(slot, 1)
        if each is not None:
            each(pc)
        tok, lens, active = np.zeros(B, np.int32), np.ones(B, np.int32), np.zeros(B, bool)
        tok[slot], lens[slot], active[slot] = t, pc.seq_lens[slot] + 1, True
        logits, pc.k_pool, pc.v_pool, _ = phi.decode_step_paged(
            cfg, params, jnp.asarray(tok), pc.k_pool, pc.v_pool, pc.tables_device(), jnp.asarray(lens),
            jnp.asarray(active))
        pc.advance_slot(slot, 1)
        out.append(np.asarray(logits[slot]))
    return np.stack(out)


# ------------------------------------------------------ hand values, the ops
def test_the_layer_map_and_lambda_init_are_the_published_ones():
    full = phi.Phi4FlashConfig()
    kinds = phi.layer_kinds(full)
    assert len(kinds) == 32 and kinds[:4] == (phi.MAMBA, phi.WINDOW, phi.MAMBA, phi.WINDOW)
    assert kinds[14:20] == (phi.MAMBA, phi.WINDOW, phi.MAMBA, phi.FULL, phi.GMU, phi.CROSS)
    assert [kinds.count(k) for k in (phi.MAMBA, phi.WINDOW, phi.FULL, phi.GMU, phi.CROSS)] == [9, 8, 1, 7, 7]
    assert (full.n_pairs, full.n_cross, full.n_mamba, full.d_inner, full.kv_heads) == (8, 7, 9, 5120, (10, 128))
    assert [k for k, _, _ in reference.layer_map(32)] == [
        {phi.MAMBA: "mamba", phi.WINDOW: "window", phi.FULL: "full", phi.GMU: "gmu", phi.CROSS: "cross"}[k] for k in kinds]
    assert phi.layer_kinds(CFG) == (phi.MAMBA, phi.WINDOW) * 2 + (phi.MAMBA, phi.FULL, phi.GMU, phi.CROSS)
    # 0.8 - 0.6 exp(-0.3 l): 0.3555 at layer 1, 0.7963 at layer 17, 0.79995 at layer 31
    assert phi.lambda_init(1) == pytest.approx(0.355509, abs=1e-6) and reference.lambda_init(1) == pytest.approx(0.355509, abs=1e-6)
    assert phi.lambda_init(17) == pytest.approx(0.796342, abs=1e-6) and phi.lambda_init(31) == pytest.approx(0.799945, abs=1e-6)
    win, full_init, cross = phi._lam_inits(full)
    assert np.allclose(win, [phi.lambda_init(l) for l in range(1, 16, 2)]) and full_init == phi.lambda_init(17)
    assert np.allclose(cross, [phi.lambda_init(l) for l in range(19, 32, 2)])


def test_the_scan_over_a_chunk_agrees_with_the_one_step_form():
    B, T, Din, N = 3, 32, 24, 4
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    u, b, c = (jax.random.normal(k, shape) for k, shape in zip(ks, ((B, T, Din), (B, T, N), (B, T, N))))
    delta = jnp.exp(jax.random.uniform(ks[3], (B, T, Din), minval=np.log(0.001), maxval=np.log(0.3)))
    delta = delta.at[1, 20:].set(0.0)  # row 1 is padding from position 20 on
    a_log, d = jnp.log(jnp.arange(1.0, N + 1))[:, None] * jnp.ones((N, Din)), jax.random.normal(ks[4], (Din,))
    s0 = jax.random.normal(ks[5], (B, N, Din))
    state, ys = s0, []
    for t in range(T):
        y, state = ssm.selective_step(u[:, t], delta[:, t], a_log, b[:, t], c[:, t], d, state)
        ys.append(y)
        if t == 19:
            at_20 = state
    y, s = ssm.selective_scan(u, delta, a_log, b, c, d, s0)
    assert float(jnp.max(jnp.abs(y - jnp.stack(ys, 1)))) < 1e-5 and float(jnp.max(jnp.abs(s - state))) < 1e-5
    # a position with Delta = 0 leaves the state exactly where it was
    assert bool(jnp.all(state[1] == at_20[1]))
    # a state of another type is rounded to it after every step (the probe a test of the tolerance uses)
    _, rounded = ssm.selective_scan(u, delta, a_log, b, c, d, s0.astype(jnp.bfloat16))
    assert rounded.dtype == jnp.bfloat16 and float(jnp.max(jnp.abs(rounded.astype(jnp.float32) - state))) > 1e-3


def test_the_conv_carries_its_tail_from_call_to_call():
    B, T, Din, K = 2, 9, 6, 4
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    u, w, bias = jax.random.normal(ks[0], (B, T, Din)), jax.random.normal(ks[1], (K, Din)), jax.random.normal(ks[2], (Din,))
    zero = jnp.zeros((B, K - 1, Din))
    whole, _ = ssm.causal_conv(u, zero, w, bias)
    first, seen = ssm.causal_conv(u[:, :5], zero, w, bias)
    tail = ssm.conv_tail(seen, jnp.asarray([5, 5]), K - 1)
    second, _ = ssm.causal_conv(u[:, 5:], tail, w, bias)
    assert float(jnp.max(jnp.abs(jnp.concatenate([first, second], 1) - whole))) < 1e-6
    assert bool(jnp.all(ssm.conv_tail(seen, jnp.asarray([0, 2]), K - 1)[0] == 0))  # n = 0 hands the old tail back


@pytest.mark.parametrize("window", [None, 5], ids=["full", "window"])
def test_padded_queries_over_paired_heads_are_the_two_softmaxes(window):
    """The attention entries that exist, given queries zero-padded to the
    pair's width over K and V stored as pairs, return P1 [V | V'] and
    P2 [V | V']: the subtraction and the norm around them are
    ``reference._differential`` (two softmaxes a pair, K and V unpadded)."""
    T, H, Hkv, Dh = 12, CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q, k, v = (jax.random.normal(key, (T, n, Dh)) for key, n in zip(ks, (H, Hkv, Hkv)))
    lp = {"lq1": jnp.full((Dh,), 0.1), "lk1": jnp.full((Dh,), 0.2), "lq2": jnp.full((Dh,), -0.1), "lk2": jnp.full((Dh,), 0.3),
          "sub_norm": 1.0 + 0.1 * jax.random.normal(ks[3], (2 * Dh,))}
    lam_init = jnp.float32(phi.lambda_init(3))
    want = reference._differential(q, k, v, window, reference._lambda(lp, lam_init), lam_init, lp["sub_norm"], CFG.norm_eps)
    pairs = lambda a: a.reshape(1, T, Hkv // 2, 2 * Dh)  # noqa: E731
    attn = attention(phi._pad_queries(q)[None], pairs(k), pairs(v), causal=True, scale=Dh ** -0.5,
                     window=None if window is None else jnp.int32(window))
    got = phi._differential(CFG, attn, lp, lam_init)[0]
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    # and the paged kernel's contract: the last position against the same cache in pages
    pool_k = pairs(k)[0].reshape(T // PAGE, PAGE, Hkv // 2, 2 * Dh).transpose(0, 2, 1, 3)
    pool_v = pairs(v)[0].reshape(T // PAGE, PAGE, Hkv // 2, 2 * Dh).transpose(0, 2, 1, 3)
    out = paged_decode_attention(phi._pad_queries(q[-1])[None], pool_k, pool_v, jnp.arange(T // PAGE)[None],
                                 jnp.asarray([T]), scale=Dh ** -0.5, window=None if window is None else jnp.int32(window))
    assert float(jnp.max(jnp.abs(phi._differential(CFG, out, lp, lam_init)[0] - want[-1]))) < 1e-5


# ------------------------------------------------- the model and the reference
@pytest.mark.parametrize("weights", ["plain", "int8"])
def test_bucketed_prefill_then_paged_decode_is_the_reference_at_every_position(weights, ids, request):
    """A prompt of 11 in a bucket of 16, then 329 decode steps: more than
    40 windows of 8 and 82 pages of the full pool, the ring of 3 pages
    coming round 27 times. Freed ring pages are poisoned (1e30: a NaN
    would survive a zero weight in a page that is written over one token
    at a time) before every step, and never move a logit."""
    params = request.getfixturevalue(weights)
    want = np.asarray(reference.logits(as_file(CFG), params, ids[:340]))
    pc = pager()
    assert np.abs(bucketed(CFG, params, pc, 1, ids[:11], 16) - want[10]).max() < TOL
    ring = pc._rings["window"][1]
    assert ring == CFG.sliding_window // PAGE + 1 == 3 and pc.pool_pages("window") == 2 * ring
    held = []

    def poison(pc):
        lo, hi = (int(a[1]) for a in pc._ring_spans("window"))
        held.append(min(hi - lo + 1, ring))  # a bucket's reservation can run a page ahead of the ring
        free = [p for p in range(ring, 2 * ring) if p not in {ring + j % ring for j in range(lo, hi + 1)}]
        if free:
            pc.k_pool["window"] = pc.k_pool["window"].at[:, jnp.asarray(free)].set(1e30)
            pc.v_pool["window"] = pc.v_pool["window"].at[:, jnp.asarray(free)].set(1e30)
        assert pc.stats()["pools"]["window"]["used"] == held[-1]

    got = decode(CFG, params, pc, 1, ids[11:340], each=poison)
    assert np.abs(got - want[11:340]).max() < TOL
    assert max(held) <= ring and min(held) >= 2  # a window of 8 lies in 2 or 3 pages of 4
    stats = pc.stats()
    assert stats["pools"]["full"]["used"] == 340 // PAGE and stats["total_blocks"] == 2 * 384 // PAGE + 2 * ring
    pc.close()


def test_the_int4_control_and_a_bfloat16_recurrence_fail_the_same_tolerance(plain, int8, ids):
    want = np.asarray(reference.logits(as_file(CFG), int8, ids[:72]))
    control = np.asarray(reference.logits(as_file(CFG), int8, ids[:72], weight_bits=4))
    assert np.abs(control - want).max() > 2 * TOL
    rounded = phi.Phi4FlashConfig.tiny(vocab_size=300, state_dtype=jnp.bfloat16)
    plain_want = np.asarray(reference.logits(as_file(CFG), plain, ids[:72]))
    pc = pager(rounded)
    assert pc.k_pool["state"]["ssm"].dtype == jnp.bfloat16
    first = bucketed(rounded, plain, pc, 0, ids[:11], 16)
    got = decode(rounded, plain, pc, 0, ids[11:72])
    assert max(np.abs(first - plain_want[10]).max(), np.abs(got - plain_want[11:72]).max()) > 2 * TOL
    pc.close()


def test_chunked_prefill_carries_the_state_and_runs_the_upper_half_once(plain, ids):
    """A prompt of 21 through chunks of 8 (three chunks, the window's old
    keys read before the third writes over them), then decode: the
    reference at every position served. The chunks that finish nothing
    return zeros: no cross-decoder ran. The slot's last occupant left a
    state; the other slot's is not touched."""
    want = np.asarray(reference.logits(as_file(CFG), plain, ids[:60]))
    pc = pager()
    pc.k_pool["state"] = jax.tree.map(lambda a: a + 5.0, pc.k_pool["state"])
    logits = chunked(CFG, plain, pc, 1, ids[:21], 8)
    assert len(logits) == 3 and not logits[0].any() and not logits[1].any()
    assert np.abs(logits[2] - want[20]).max() < TOL
    assert bool(jnp.all(pc.k_pool["state"]["ssm"][:, 0] == 5.0)) and bool(jnp.all(pc.k_pool["state"]["conv"][:, 0] == 5.0))
    assert np.abs(decode(CFG, plain, pc, 1, ids[21:60]) - want[21:60]).max() < TOL
    pc.close()


def test_a_buckets_padding_leaves_the_state_where_seq_len_put_it(plain, ids):
    out = []
    for bucket in (12, 16, 32):
        tokens = np.full((1, bucket), 9, np.int32)
        tokens[0, :11] = ids[:11]
        last, cache = phi.prefill(CFG, plain, jnp.asarray(tokens), phi.KVCache.create(CFG, 1, bucket), jnp.asarray([11]))
        out.append((np.asarray(last), jax.tree.map(np.asarray, cache.k["state"])))
    for last, state in out[1:]:
        assert np.abs(last - out[0][0]).max() < 1e-5
        assert np.abs(state["ssm"] - out[0][1]["ssm"]).max() < 1e-6 and np.abs(state["conv"] - out[0][1]["conv"]).max() == 0


def test_a_slot_is_reused_after_a_longer_occupant(plain, ids):
    pc = pager()
    bucketed(CFG, plain, pc, 0, ids[100:131], 32, seq_id=1)
    decode(CFG, plain, pc, 0, ids[131:190])
    pc.free_slot(0)
    want = np.asarray(reference.logits(as_file(CFG), plain, ids[:40]))
    assert np.abs(bucketed(CFG, plain, pc, 0, ids[:6], 16, seq_id=2) - want[5]).max() < TOL
    assert np.abs(decode(CFG, plain, pc, 0, ids[6:40]) - want[6:40]).max() < TOL
    pc.close()


def test_a_done_or_idle_rows_state_is_not_advanced_by_a_block(plain, ids):
    """``decode_block_paged`` over three slots: slot 0 live, slot 1 done
    (its budget spent), slot 2 never dispatched. Only slot 0's state
    moves; the counters count its steps alone."""
    pc = pager(slots=3)
    for slot in range(3):
        bucketed(CFG, plain, pc, slot, ids[slot * 20:slot * 20 + 9], 16, seq_id=slot + 1)
        assert pc.try_reserve_slot(slot, 4)
    before = jax.tree.map(np.asarray, pc.k_pool["state"])
    n = np.full(3, 9, np.int32)
    state = batch_ops.make_decode_state(ids[[9, 29, 49]], n, [False, True, False], [50, 0, 50], [-1] * 3, [0.0] * 3, [0] * 3,
                                        [1.0] * 3, jax.random.PRNGKey(0))
    packed, pc.k_pool, pc.v_pool, state = batch_ops.decode_block_paged(
        CFG, plain, pc.k_pool, pc.v_pool, state, pc.tables_device(), jnp.asarray([True, True, False]), 4)
    after = jax.tree.map(np.asarray, pc.k_pool["state"])
    for key in ("ssm", "conv"):
        assert (after[key][:, 1:] == before[key][:, 1:]).all() and (after[key][:, 0] != before[key][:, 0]).any()
    stats = dict(zip(phi.STEP_STATS, batch_ops.block_stats(np.asarray(packed), 3, phi.step_stats_len(CFG)).tolist()))
    lens = [10, 11, 12, 13]  # the positions slot 0's four steps held
    assert stats == {"attn_full": (CFG.n_cross + 1) * sum(lens), "ssm_rows": 4,
                     "attn_win": CFG.n_pairs * sum(min(n, CFG.sliding_window) for n in lens)}
    pc.close()


def test_the_other_families_pools_keep_their_shapes():
    for module, cfg in ((llama, llama.LlamaConfig.tiny()), (cohere2_moe, cohere2_moe.Cohere2MoeConfig.tiny())):
        cache = PagedKVCache(cfg, num_pages=6, page_size=8, max_slots=2, max_seq_len=24,
                             page_shapes=module.page_shapes(cfg, 8))
        assert cache.k_pool.shape == cache.v_pool.shape == (cfg.n_layers, 7, cfg.n_kv_heads, 8, cfg.head_dim)
        assert cache.tables_device().shape == (2, 3) and "pools" not in cache.stats() and cache.ring_pools == ()
        cache.close()
    cfg = deepseek_v32.DeepseekV32Config.tiny()
    cache = PagedKVCache(cfg, num_pages=6, page_size=8, max_slots=2, max_seq_len=24,
                         page_shapes=deepseek_v32.page_shapes(cfg, 8))
    assert cache.k_pool.shape == (cfg.n_layers, 7, 1, 8, cfg.row_width) and cache.v_pool.shape == (cfg.n_layers, 7, 1, 8, cfg.index_head_dim)
    assert not hasattr(deepseek_v32, "cache_spec") and not hasattr(llama, "cache_spec")
    cache.close()
    # this model's: a ring of 3 pages a slot in 2 window layers, one full layer, a state a slot
    pc = pager()
    assert pc.k_pool["window"].shape == pc.v_pool["window"].shape == (2, 2 * 3 + 1, 1, PAGE, 32)
    assert pc.k_pool["full"].shape == (1, 2 * 384 // PAGE + 1, 1, PAGE, 32)
    assert pc.k_pool["state"]["ssm"].shape == (3, 2, 4, 128) and pc.k_pool["state"]["ssm"].dtype == jnp.float32
    assert pc.k_pool["state"]["conv"].shape == (3, 2, 3, 128) and set(pc.v_pool) == {"window", "full"}
    with pytest.raises(NotImplementedError, match="several pools"):
        pc.read_span(0, 0, 4)
    pc.close()


# ------------------------------------------------------------- the engine
def engine_settings(**kw):
    settings = dict(max_slots=3, max_seq_len=96, prefill_buckets=(16,), multi_step=4,
                    kv_page_size=PAGE, prefill_chunk_tokens=8)
    settings.update(kw)
    return EngineConfig(**settings)


@pytest.mark.parametrize("settings, lora, sentence", [
    (dict(spec_tokens=2, multi_step=None), None, "no speculative verify program"),
    (dict(), object(), "serves no LoRA adapters"),
    (dict(prefix_cache_entries=4), None, "keeps no prefix cache"),
    (dict(kv_spill_bytes=1 << 20), None, "spills no KV"),
    (dict(role="prefill"), None, "unified replicas only"),
    (dict(prefill_chunk_tokens=16), None, "may not exceed the sliding window"),
], ids=["speculative", "lora", "prefix_cache", "spill", "role", "chunk"])
def test_engines_the_model_has_no_program_for_are_refused_at_construction(plain, settings, lora, sentence):
    with pytest.raises(ValueError, match=sentence):
        ServingEngine(CFG, plain, engine_settings(**settings), ByteTokenizer(300), lora=lora)


def test_the_seam_finds_the_module_and_its_counters():
    assert batch_ops.model_of(CFG) is phi and phi.step_stats_len(CFG) == 3
    assert phi.unserved(engine_settings(), None, CFG) is None and phi.CHUNK_TAKES_FINISH


def test_the_model_is_served_behind_an_app_over_http_with_its_spans_and_counters(plain, monkeypatch):
    """POST /generate and the SSE route through a real App, a bucketed and
    a chunked prompt, 40 tokens each (five windows): the tokens are the
    reference's greedy choice; the commit spans carry ``attn_full``,
    ``attn_win`` and ``ssm_rows``, the dispatch spans ``win_pages_held``
    and ``win_pages_freed``, the prefill spans and ragged dispatches
    ``self_tokens`` and ``cross_tokens``; /metrics and health show the
    pools and the counters."""
    import gofr_tpu
    from gofr_tpu.config import MapConfig
    from gofr_tpu.serving import engine as engine_mod
    from gofr_tpu.serving.handlers import register_generation_routes
    from gofr_tpu.testutil import get_free_port

    http_port, metrics_port = get_free_port(), get_free_port()
    app = gofr_tpu.App(MapConfig({"HTTP_PORT": str(http_port), "METRICS_PORT": str(metrics_port),
                                  "APP_NAME": "phi4flash-test", "LOG_LEVEL": "WARN"}, use_env=False))
    tokenizer = ByteTokenizer(300)
    engine = ServingEngine(CFG, plain, engine_settings(), tokenizer, metrics=app.container.metrics_manager,
                           logger=app.container.logger)
    seen = []
    real = engine_mod._StepPhase.set
    monkeypatch.setattr(engine_mod._StepPhase, "set", lambda self, **kw: (seen.append((self._phase, kw)), real(self, **kw))[1])
    register_generation_routes(app, engine)
    thread = threading.Thread(target=app.run, daemon=True)
    thread.start()
    base = f"http://127.0.0.1:{http_port}"

    def post(path, body):
        req = urllib.request.Request(base + path, data=json.dumps(body).encode(), method="POST",
                                     headers={"Content-Type": "application/json"})
        return urllib.request.urlopen(req, timeout=300)

    try:
        deadline = time.monotonic() + 60
        while True:
            try:
                urllib.request.urlopen(base + "/.well-known/alive", timeout=1).close()
                break
            except OSError:
                assert time.monotonic() < deadline and thread.is_alive()
                time.sleep(0.05)
        short, long = "short", "a prompt of three chunks"  # 6 tokens with BOS: bucketed; 25: chunks of 8
        answers, texts = {}, {}
        for prompt in (short, long):
            with post("/generate/stream", {"prompt": prompt, "max_tokens": 40, "temperature": 0.0}) as resp:
                frames = [json.loads(line[6:]) for line in resp.read().decode().splitlines() if line.startswith("data: {")]
            answers[prompt] = [f["token"] for f in frames if "token" in f]
            with post("/generate", {"prompt": prompt, "max_tokens": 40, "temperature": 0.0}) as resp:
                texts[prompt] = json.loads(resp.read())["data"]
        health = json.loads(urllib.request.urlopen(base + "/.well-known/health", timeout=10).read())
        metrics = urllib.request.urlopen(f"http://127.0.0.1:{metrics_port}/metrics", timeout=10).read().decode()
    finally:
        app.stop()
        thread.join(timeout=60)

    for prompt, served in answers.items():
        prompt_ids = tokenizer.encode(prompt)
        assert len(served) == 40
        gaps = reference.served_gaps(as_file(CFG), plain, prompt_ids, served)["served"]
        assert gaps.max() < TOL, (prompt, gaps)
        assert texts[prompt]["usage"]["completion_tokens"] == 40 and texts[prompt]["text"] == tokenizer.decode(served)
    commits = [kw for phase, kw in seen if phase == "commit" and "ssm_rows" in kw]
    assert any(kw["ssm_rows"] for kw in commits)
    # one request at a time: a live step reads its whole context in the full layer and its reader,
    # and at most a window of it in each of the two window layers
    assert all(kw["attn_win"] <= CFG.n_pairs * CFG.sliding_window * kw["ssm_rows"] for kw in commits)
    assert all(kw["attn_full"] * CFG.n_pairs >= kw["attn_win"] * (CFG.n_cross + 1) for kw in commits)
    assert any(kw["attn_full"] * CFG.n_pairs > kw["attn_win"] * (CFG.n_cross + 1) for kw in commits)  # the window binds
    turns = [kw for phase, kw in seen if phase == "dispatch" and "win_pages_held" in kw]
    assert turns and all(0 <= kw["win_pages_held"] <= 3 for kw in turns) and sum(kw["win_pages_freed"] for kw in turns) >= 4 * 8
    assert any(kw.get("win_rows") for phase, kw in seen if phase == "dispatch")
    halves = [kw for phase, kw in seen if "self_tokens" in kw]
    bucketed_prefills = [kw for phase, kw in seen if phase == "prefill" and "self_tokens" in kw]
    assert [kw["self_tokens"] for kw in bucketed_prefills] == [6, 6] and all(kw["cross_tokens"] == 1 for kw in bucketed_prefills)
    ragged = [kw for kw in halves if kw not in bucketed_prefills]
    assert [kw["self_tokens"] for kw in ragged] == [8, 8, 8, 1] * 2 and [kw["cross_tokens"] for kw in ragged] == [0, 0, 0, 1] * 2
    for line in ('app_prefill_positions_total{part="self"} 62', 'app_prefill_positions_total{part="cross"} 4',
                 "app_ssm_state_resets_total 4", 'app_kv_pool_pages{pool="window",state="total"} 9',
                 'app_kv_pool_pages{pool="full",state="total"} 72'):
        assert any(l.replace(".0", "").startswith(line) for l in metrics.splitlines()), line
    pages = health["data"]["details"]["serving"]["details"]["kv_pages"]
    assert pages["total_blocks"] == 81 and pages["pools"] == {"window": {"used": 0, "total": 9}, "full": {"used": 0, "total": 72}}


def test_a_preempted_request_resumes_by_prefilling_what_it_emitted(plain):
    """No snapshot of the state is kept: a preempted row is requeued and
    prefills prompt + emitted tokens (here 30: through chunks), then
    decodes on. The tokens are those of an undisturbed run."""
    tokenizer = ByteTokenizer(300)
    ctrl_engine = ServingEngine(CFG, plain, engine_settings(), tokenizer)
    ctrl_engine.start()
    try:
        ctrl = ctrl_engine.submit("resume me", max_new_tokens=48, temperature=0.0).result(timeout=300)
    finally:
        ctrl_engine.stop()
    engine = ServingEngine(CFG, plain, engine_settings(), tokenizer)
    got: list = []
    preempted = threading.Event()

    def maybe_preempt() -> bool:
        slot = next((s for s, r in enumerate(engine.slots) if r is not None), None)
        if preempted.is_set() or slot is None or len(got) < 20:
            return False
        if engine._slot_in_flight(slot, engine.slots[slot]):
            engine._preempt_pending.add(slot)  # as the ladder does: stop feeding the row, its blocks drain
            return False
        engine._preempt_pending.discard(slot)
        preempted.set()
        engine._preempt(slot)
        return True

    engine._maybe_preempt = maybe_preempt  # the ladder's own trigger needs a tenant registry; the page-out is what is tested
    engine.start()
    try:
        fut = engine.submit("resume me", max_new_tokens=48, temperature=0.0, stream_cb=lambda t, s, d: got.append(t))
        low = fut.result(timeout=300)
        timeline = engine.timeline.get(fut.request_id)
    finally:
        engine.stop()
    assert preempted.is_set() and any(p.startswith("preempted") for p in timeline.phases)
    assert low.token_ids == ctrl.token_ids and [t for t in got if t >= 0] == list(ctrl.token_ids)  # -1: the stream's end
