"""``models/nemotron_h.py`` and ``ops/ssd.py`` against the plain reference
(``benchmarks/harness/nemotron_h_reference.py``: float32, the Mamba-2
recurrence one position at a time from a zero state, the conv a literal
sum over the sequence, attention unpaged, every expert dense under its
gate, nothing imported from the program) at tiny widths with the
published structure on the CPU: 14 layers in units ``M E`` and ``M * E``
(6 Mamba, 6 expert, 2 attention), hidden 64, 4 / 2 heads of 16, 8 Mamba
heads of 8 in 2 groups with a state of 16, 16 relu² experts of 24 (top 4,
a seeded correction bias, routed scaling 2.5) beside a shared one of 48,
sub-chunks of 8, pages of 4 — seeded weights.

Tolerances, each with its reason:

- ``TOL`` 1e-3 on logits of deviation about 0.6: the program in float32
  differs from the reference by the order of sums only — the chunked SSD
  against the per-position recurrence, the state step against the scan,
  paged against unpaged attention (readings 1e-6 to 1e-5 through prefill,
  chunks and decode); the int8 tree by the same (both de-quantise the same
  integers). A bfloat16 state fails it, and so does the int4 control.
- ``1e-5`` relative between the SSD scan's two forms and the per-position
  recurrence: float32 sums in another order.
"""

import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lowered_digests
from benchmarks.harness import nemotron_h_reference as reference
from gofr_tpu.models import nemotron_h as nh
from gofr_tpu.models.llama import _mm, quantize_weight
from gofr_tpu.ops import moe, ssd
from gofr_tpu.serving import ByteTokenizer, EngineConfig, ServingEngine
from gofr_tpu.serving import batch as batch_ops
from gofr_tpu.serving import engine as engine_mod
from gofr_tpu.serving.kv_cache import PagedKVCache

CFG = nh.NemotronHConfig.tiny(vocab_size=300)
PAGE, TOL = 4, 1e-3


def as_file(cfg):
    """The program's config under the published keys the reference reads."""
    return {"hybrid_override_pattern": cfg.pattern, "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim, "mamba_num_heads": cfg.mamba_heads,
            "mamba_head_dim": cfg.mamba_head_dim, "n_groups": cfg.n_groups, "ssm_state_size": cfg.d_state,
            "conv_kernel": cfg.conv_kernel, "norm_eps": cfg.norm_eps, "num_experts_per_tok": cfg.top_k,
            "routed_scaling_factor": cfg.routed_scaling, "moe_intermediate_size": cfg.d_ff_expert,
            "moe_shared_expert_intermediate_size": cfg.d_ff_shared, "n_group": 1, "topk_group": 1,
            "vocab_size": cfg.vocab_size}


@pytest.fixture(scope="module")
def plain():
    return nh.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def int8(plain):
    return nh.quantize_params(plain)


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(7).integers(0, CFG.vocab_size, 200).astype(np.int32)


@pytest.fixture(scope="module")
def want(plain, ids):
    return np.asarray(reference.logits(as_file(CFG), plain, ids[:120]))


def pager(cfg=CFG, slots=2, max_seq=128):
    return PagedKVCache(cfg, num_pages=slots * max_seq // PAGE, page_size=PAGE, max_slots=slots,
                        max_seq_len=max_seq, spec=nh.cache_spec(cfg, PAGE))


def bucketed(cfg, params, pc, slot, prompt, bucket, seq_id=1):
    """Prefill ``prompt`` in a bucket and write it into ``slot``; the last position's logits."""
    tokens = np.zeros((1, bucket), np.int32)
    tokens[0, :len(prompt)] = prompt
    last, cache = nh.prefill(cfg, params, jnp.asarray(tokens), nh.KVCache.create(cfg, 1, bucket),
                             jnp.asarray([len(prompt)]))
    pc.alloc_slot(slot, seq_id=seq_id, prompt_len=len(prompt), reserve_tokens=bucket)
    pc.write_prefill(slot, *nh.prefill_slabs(cache))
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
        active, cap = np.zeros(B, bool), np.zeros(B, np.int32)
        active[slot], cap[slot] = True, pc.owned_capacity(slot)
        logits, pc.k_pool, pc.v_pool = nh.decode_chunk_paged(
            cfg, params, jnp.asarray(toks), pc.k_pool, pc.v_pool, pc.tables_device(), jnp.asarray(starts),
            jnp.asarray(active), jnp.asarray(cap))
        pc.advance_slot(slot, n)
        start += n
        out.append(np.asarray(logits[slot, 0]))
    return out


def decode(cfg, params, pc, slot, tokens):
    """Feed ``tokens`` one step at a time to ``slot`` (the other slots idle); logits [len, V] and the counters."""
    B, out, stats = pc.max_slots, [], 0
    for t in tokens:
        assert pc.try_reserve_slot(slot, 1)
        tok, lens, active = np.zeros(B, np.int32), np.ones(B, np.int32), np.zeros(B, bool)
        tok[slot], lens[slot], active[slot] = t, pc.seq_lens[slot] + 1, True
        logits, pc.k_pool, pc.v_pool, counted = nh.decode_step_paged(
            cfg, params, jnp.asarray(tok), pc.k_pool, pc.v_pool, pc.tables_device(), jnp.asarray(lens),
            jnp.asarray(active))
        pc.advance_slot(slot, 1)
        out.append(np.asarray(logits[slot]))
        stats = stats + np.asarray(counted)
    return np.stack(out), stats


# ------------------------------------------------------ hand values, the ops
def test_the_layer_map_is_the_published_one():
    full = nh.NemotronHConfig()
    kinds = nh.layer_kinds(full.pattern)
    assert (full.n_mamba, full.n_moe, full.n_attn, full.n_layers) == (23, 23, 6, 52)
    assert [l for l, k in enumerate(kinds) if k == nh.ATTN] == [5, 12, 19, 26, 33, 42]
    lead, starts, n_pairs = nh._blocks(full)
    # M0, pairs (E u, M u+1): two before the first attention layer, then 3, 3, 3, 3, 4 and 4, then E22
    assert lead == 2 and starts.tolist() == [2, 5, 8, 11, 14, 18] and n_pairs.tolist() == [3, 3, 3, 3, 4, 4]
    assert 1 + lead + sum(n_pairs) == 23 and (full.d_inner, full.conv_dim) == (4096, 6144)
    assert (full.held_experts, full.first_expert, nh.step_stats_len(full)) == (128, 0, 131)
    pools, state = nh.cache_spec(full, 16)
    assert pools == (("full", 6, (2, 16, 128), (2, 16, 128), None),)
    assert state == {"ssm": (23, (64, 64, 128), jnp.float32), "conv": (23, (3, 6144), jnp.bfloat16)}
    assert 23 * 64 * 64 * 128 * 4 == 48_234_496 and 23 * 3 * 6144 * 2 == 847_872  # a slot's state, B
    with pytest.raises(ValueError, match="units 'ME' and 'M\\*E'"):
        nh.NemotronHConfig.tiny(pattern="MEEM*E")


@jax.jit
def _recurrence(x, dt, a, b, c, s):
    """The definition, one position at a time: (y [B, T, H, P], the last state)."""
    r = x.shape[2] // b.shape[2]

    def position(s, xs):
        x_t, d_t, b_t, c_t = xs
        bh, ch = jnp.repeat(b_t, r, axis=1), jnp.repeat(c_t, r, axis=1)
        s = jnp.exp(d_t * a)[..., None, None] * s + (d_t[..., None] * x_t)[..., None] * bh[:, :, None, :]
        return s, jnp.sum(s * ch[:, :, None, :], axis=-1)

    s, ys = jax.lax.scan(position, s, tuple(jnp.swapaxes(v, 0, 1) for v in (x, dt, b, c)))
    return jnp.swapaxes(ys, 0, 1), s


@pytest.mark.parametrize("chunk, cuts", [(8, ()), (8, (13, 24)), (5, (7,)), (64, (30,))],
                         ids=["one-call", "three-calls", "ragged-sub-chunks", "one-sub-chunk"])
def test_the_chunked_scan_is_the_per_position_recurrence(chunk, cuts):
    """``ssd_chunk_scan`` in sub-chunks of ``chunk`` over 37 positions,
    from a carried start state, in one call or cut into several (each
    starting from the state the last returned): the recurrence's y and
    last state. Row 1's last 7 positions are padding (Delta = 0): its
    state after them is its state at position 30, exactly as it was."""
    B, T, H, P, G, N = 2, 37, 8, 4, 2, 16
    k = jax.random.split(jax.random.PRNGKey(3), 6)
    x = jax.random.normal(k[0], (B, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, T, H)) - 1.0).at[1, 30:].set(0.0)
    a = -jnp.exp(jax.random.normal(k[2], (H,)))
    b, c = jax.random.normal(k[3], (B, T, G, N)), jax.random.normal(k[4], (B, T, G, N))
    s0 = jax.random.normal(k[5], (B, H, P, N))
    want_y, want_s = _recurrence(x, dt, a, b, c, s0)
    bounds = (0,) + cuts + (T,)
    ys, s = [], s0
    for lo, hi in zip(bounds, bounds[1:]):
        y, s = ssd.ssd_chunk_scan(x[:, lo:hi], dt[:, lo:hi], a, b[:, lo:hi], c[:, lo:hi], s, chunk=chunk)
        ys.append(y)
    got = jnp.concatenate(ys, 1)
    assert float(jnp.abs(got - want_y).max()) <= 1e-5 * float(jnp.abs(want_y).max())
    assert float(jnp.abs(s - want_s).max()) <= 1e-5 * float(jnp.abs(want_s).max())
    _, at30 = _recurrence(x[1:, :30], dt[1:, :30], a, b[1:, :30], c[1:, :30], s0[1:])
    assert float(jnp.abs(s[1] - at30[0]).max()) <= 1e-5 * float(jnp.abs(at30).max())


@pytest.mark.parametrize("live", [[True, False, True, True, False], [False] * 5, [True] * 5],
                         ids=["some-live", "none-live", "all-live"])
def test_the_state_step_kernel_is_its_xla_form(live):
    """``ssd_state_step`` under the Pallas interpreter against
    ``ssd_state_step_ref``: layer 1 of every live row advanced, y the
    recurrence's; every other layer and every row that is not live left
    bit for bit as it was, y 0 there."""
    L, B, H, P, G, N = 3, 5, 8, 8, 2, 128
    k = jax.random.split(jax.random.PRNGKey(4), 6)
    pool = jax.random.normal(k[0], (L, B, H, P, N))
    u, decay = jax.random.normal(k[1], (B, H, P)), jax.random.uniform(k[2], (B, H))
    b, c = jax.random.normal(k[3], (B, G, N)), jax.random.normal(k[4], (B, G, N))
    live = jnp.asarray(live)
    want_y, want_pool = ssd.ssd_state_step_ref(pool, 1, live, u, decay, b, c)
    got_y, got_pool = ssd.ssd_state_step(pool, jnp.int32(1), live, u, decay, b, c, interpret=True)
    assert float(jnp.abs(got_y - want_y).max()) <= 1e-5 * (1 + float(jnp.abs(want_y).max()))
    assert float(jnp.abs(got_pool - want_pool).max()) <= 1e-5 * float(jnp.abs(want_pool).max())
    dead = ~np.asarray(live)
    assert (np.asarray(got_pool)[:, dead] == np.asarray(pool)[:, dead]).all()
    assert (np.asarray(got_pool)[[0, 2]] == np.asarray(pool)[[0, 2]]).all() and (np.asarray(got_y)[dead] == 0).all()


@pytest.mark.parametrize("T", [12, 200], ids=["every-row", "grouped"])
def test_the_eight_shares_of_a_relu2_layer_add_up_to_the_uncut_layer(T):
    """16 relu² experts (top 4 of the 16, bias, scaling 2.5) and a shared
    one: each of eight chips holds two and computes ``held_experts`` for
    them, the shared expert counted once; the shares add up to the layer
    held whole, and to the dense sum under the gates. Int8 stacks at a
    width of no whole lane tile, padded as served."""
    D, F, E = 64, 24, 16
    k = jax.random.split(jax.random.PRNGKey(6), 7)
    relu2 = lambda kk, n, f: {"w_up": jax.random.normal(kk[0], (n, D, f)) / np.sqrt(D),  # noqa: E731
                              "w_down": jax.random.normal(kk[1], (n, f, D)) / np.sqrt(f)}
    stacks = nh.pad_experts({n: quantize_weight(w, axis=-2) for n, w in relu2(k[:2], E, F).items()})
    shared = nh.pad_experts({n: quantize_weight(w, axis=-2) for n, w in relu2(k[2:4], 1, 2 * F).items()})
    none = {n: jax.tree.map(lambda a: a[:0], w) for n, w in shared.items()}
    h = jax.random.normal(k[4], (T, D), jnp.float32)
    gates = moe.sigmoid_topk_gates(h, jax.random.normal(k[5], (D, E)) / 8, 4,
                                   bias=0.1 * jax.random.normal(k[6], (E,)), scale=2.5)
    held = jax.jit(lambda g, st, sh, first: moe.held_experts(h, g, st, sh, first, _mm, top_k=4)[0])
    whole = held(gates, stacks, shared, 0)
    parts = [held(gates, jax.tree.map(lambda a: a[2 * c:2 * c + 2], stacks), none, 2 * c) for c in range(8)]
    once = held(jnp.zeros_like(gates), stacks, shared, 0)
    assert moe.groups_rows(T, E, 4) is (T > moe.RIDGE_ROWS)
    assert float(jnp.abs(sum(parts) + once - whole).max()) <= 1e-4 * float(jnp.abs(whole).max())
    dense = jax.jit(lambda: sum(gates[:, e:e + 1] * moe._ffn(h, stacks, e, _mm, None) for e in range(E))
                    + moe._ffn(h, shared, 0, _mm, None))()
    assert float(jnp.abs(dense - whole).max()) <= 1e-4 * float(jnp.abs(whole).max())
    assert stacks["w_up"]["q"].shape[-1] == 128 and not np.asarray(stacks["w_up"]["q"][..., F:]).any()


# ------------------------------------------------- the model and the reference
@pytest.mark.parametrize("weights", ["plain", "int8"])
def test_bucketed_prefill_then_paged_decode_is_the_reference_at_every_position(weights, ids, want, request):
    """A prompt of 11 in a bucket of 16, then 49 decode steps through the
    paged pool: the reference's logits at every position served; the
    counters are what ran — 6 Mamba layers and 2 attention layers a step."""
    params = request.getfixturevalue(weights)
    if weights == "int8":
        want = np.asarray(reference.logits(as_file(CFG), params, ids[:120]))
    pc = pager()
    assert np.abs(bucketed(CFG, params, pc, 1, ids[:11], 16) - want[10]).max() < TOL
    got, stats = decode(CFG, params, pc, 1, ids[11:60])
    assert np.abs(got - want[11:60]).max() < TOL
    E = CFG.n_held
    attn_full, ssm_rows = stats[E + 1:].tolist()
    assert ssm_rows == CFG.n_mamba * 49 and attn_full == CFG.n_attn * sum(range(12, 61))
    assert stats[:E].sum() == 49 * CFG.top_k * CFG.n_moe
    assert stats[E] == stats[:E].sum()  # one row: its own 4 experts a layer are read, on the own-rows path
    pc.close()


@pytest.mark.parametrize("chunk", [8, 5], ids=["chunk8", "chunk5"])
def test_chunked_prefill_carries_the_state_across_every_boundary(plain, ids, want, chunk):
    """A prompt of 37 through chunks of 8 (sub-chunks of 8: boundaries at
    the sub-chunks') or 5 (boundaries inside them, every offset mod 4 of
    the conv), then decode: the reference at every position served. The
    slot's last occupant left a state; a chunk at 0 zeroes it, and the
    other slot's is not touched."""
    pc = pager()
    pc.k_pool["state"] = jax.tree.map(lambda a: a + 5.0, pc.k_pool["state"])
    logits = chunked(CFG, plain, pc, 1, ids[:37], chunk)
    assert len(logits) == -(-37 // chunk)
    for n, got in zip(list(range(chunk, 37, chunk)) + [37], logits):  # each chunk's head at its last position
        assert np.abs(got - want[n - 1]).max() < TOL
    assert all(bool(jnp.all(a[:, 0] == 5.0)) for a in jax.tree.leaves(pc.k_pool["state"]))
    got, _ = decode(CFG, plain, pc, 1, ids[37:70])
    assert np.abs(got - want[37:70]).max() < TOL
    pc.close()


def test_a_buckets_padding_leaves_the_state_where_seq_len_put_it(plain, ids):
    out = []
    for bucket in (12, 16, 32):
        tokens = np.full((1, bucket), 9, np.int32)
        tokens[0, :11] = ids[:11]
        last, cache = nh.prefill(CFG, plain, jnp.asarray(tokens), nh.KVCache.create(CFG, 1, bucket), jnp.asarray([11]))
        out.append((np.asarray(last), jax.tree.map(np.asarray, cache.k["state"])))
    for last, state in out[1:]:
        assert np.abs(last - out[0][0]).max() < 1e-5
        assert all(np.abs(a - b).max() <= 1e-5 * np.abs(b).max() for a, b in zip(jax.tree.leaves(state),
                                                                                   jax.tree.leaves(out[0][1])))


def test_a_bfloat16_state_and_the_int4_control_fail_the_tolerance(plain, int8, ids, want):
    """The same prompt and decode with the SSD state stored in bfloat16
    (rounded after every step): it leaves the reference by more than the
    tolerance, and so does the int4 control against the int8 reference."""
    rounded = dataclasses.replace(CFG, state_dtype=jnp.bfloat16)
    pc = pager(rounded)
    first = bucketed(rounded, plain, pc, 0, ids[:11], 16)
    got, _ = decode(rounded, plain, pc, 0, ids[11:60])
    assert max(np.abs(first - want[10]).max(), np.abs(got - want[11:60]).max()) > 2 * TOL
    pc.close()
    file = as_file(CFG)
    want8 = np.asarray(reference.logits(file, int8, ids[:64]))
    assert np.abs(np.asarray(reference.logits(file, int8, ids[:64], weight_bits=4)) - want8).max() > 2 * TOL


def test_a_done_or_idle_rows_state_is_not_advanced_by_a_block(plain, ids):
    """``decode_block_paged`` over three slots: slot 0 live, slot 1 done
    (its budget spent), slot 2 never dispatched. Only slot 0's SSD states
    and conv tails move; ``ssm_rows`` counts its steps alone."""
    pc = pager(slots=3)
    for slot in range(3):
        bucketed(CFG, plain, pc, slot, ids[slot * 20:slot * 20 + 9], 16, seq_id=slot + 1)
        assert pc.try_reserve_slot(slot, 4)
    before = jax.tree.map(np.asarray, pc.k_pool["state"])
    n = np.full(3, 9, np.int32)
    state = batch_ops.make_decode_state(ids[[9, 29, 49]], n, [False, True, False], [50, 0, 50], [-1] * 3, [0.0] * 3,
                                        [0] * 3, [1.0] * 3, jax.random.PRNGKey(0))
    packed, pc.k_pool, pc.v_pool, state = batch_ops.decode_block_paged(
        CFG, plain, pc.k_pool, pc.v_pool, state, pc.tables_device(), jnp.asarray([True, True, False]), 4)
    for key, after in pc.k_pool["state"].items():
        after = np.asarray(after)
        assert (after[:, 1:] == before[key][:, 1:]).all() and (after[:, 0] != before[key][:, 0]).any(), key
    stats = batch_ops.block_stats(np.asarray(packed), 3, nh.step_stats_len(CFG))
    E = CFG.n_held
    assert dict(zip(nh.STEP_STATS, stats[E + 1:].tolist())) == {
        "attn_full": CFG.n_attn * sum([10, 11, 12, 13]), "ssm_rows": CFG.n_mamba * 4}
    assert stats[:E].sum() == 4 * CFG.top_k * CFG.n_moe
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
    (dict(prefix_cache_entries=4), None, "46 MiB a slot"),
    (dict(kv_spill_bytes=1 << 20), None, "spills no KV"),
    (dict(role="prefill"), None, "unified replicas only"),
], ids=["speculative", "lora", "prefix_cache", "spill", "role"])
def test_engines_the_model_has_no_program_for_are_refused_at_construction(plain, settings, lora, sentence):
    with pytest.raises(ValueError, match=sentence):
        ServingEngine(CFG, plain, engine_settings(**settings), ByteTokenizer(300), lora=lora)


def test_the_seam_finds_the_module_its_store_and_its_counters():
    assert batch_ops.model_of(CFG) is nh and nh.step_stats_len(CFG) == CFG.n_held + 3
    assert nh.unserved(engine_settings(), None, CFG) is None and not hasattr(nh, "CHUNK_TAKES_FINISH")
    pc = pager()
    assert set(pc.k_pool) == {"full", "state"} and set(pc.v_pool) == {"full"}
    assert pc.k_pool["full"].shape == (CFG.n_attn, 2 * 128 // PAGE + 1, CFG.n_kv_heads, PAGE, CFG.head_dim)
    assert pc.k_pool["state"]["ssm"].shape == (CFG.n_mamba, 2, CFG.mamba_heads, CFG.mamba_head_dim, CFG.d_state)
    assert pc.k_pool["state"]["ssm"].dtype == jnp.float32
    assert pc.k_pool["state"]["conv"].shape == (CFG.n_mamba, 2, CFG.conv_kernel - 1, CFG.conv_dim)
    pc.close()


def test_rows_at_different_stages_are_batched_and_each_is_the_reference(plain, monkeypatch):
    """Four requests on three slots at once — two prompts that prefill in a
    bucket, two that prefill over chunks of 8 while the others decode, one
    queued behind them: each answer is the reference's greedy choice. The
    prefill and chunk dispatch spans carry the positions the SSD scan
    advanced (``ssd_positions``: the prompts' tokens, every one once); the
    commit spans ``ssm_rows`` (the Mamba layers times the live
    row-steps) and ``attn_full``."""
    tokenizer = ByteTokenizer(300)
    engine = ServingEngine(CFG, plain, engine_settings(), tokenizer)
    seen = []
    real = engine_mod._StepPhase.set
    monkeypatch.setattr(engine_mod._StepPhase, "set", lambda self, **kw: (seen.append((self._phase, kw)), real(self, **kw))[1])
    prompts = ["short", "a prompt of three chunks", "hi", "another prompt that chunks"]
    engine.start()
    try:
        futures = [engine.submit(p, max_new_tokens=24, temperature=0.0) for p in prompts]
        results = [f.result(timeout=300) for f in futures]
    finally:
        engine.stop()
    for prompt, res in zip(prompts, results):
        assert len(res.token_ids) == 24
        gaps = reference.served_gaps(as_file(CFG), plain, tokenizer.encode(prompt), list(res.token_ids))
        assert gaps["served_tokens"].max() < TOL, (prompt, gaps["served_tokens"])
    scanned = sum(kw[nh.SCAN_POSITIONS] for _, kw in seen if nh.SCAN_POSITIONS in kw)
    assert scanned == sum(len(tokenizer.encode(p)) for p in prompts)
    assert any(phase == "dispatch" and nh.SCAN_POSITIONS in kw for phase, kw in seen)
    commits = [kw for phase, kw in seen if phase == "commit" and "ssm_rows" in kw]
    assert all(kw["ssm_rows"] % CFG.n_mamba == 0 and kw["moe_rows"] == kw["ssm_rows"] // CFG.n_mamba * CFG.n_moe * CFG.top_k
               for kw in commits)
    assert sum(kw["ssm_rows"] for kw in commits) >= CFG.n_mamba * 4 * 23 and any(kw["attn_full"] for kw in commits)


def test_a_preempted_request_resumes_by_prefilling_what_it_emitted(plain):
    """No snapshot of the state is kept: a preempted row is requeued and
    prefills prompt + emitted tokens (here 30: through chunks), then
    decodes on. The tokens are those of an undisturbed run."""
    tokenizer = ByteTokenizer(300)
    ctrl_engine = ServingEngine(CFG, plain, engine_settings(), tokenizer)
    ctrl_engine.start()
    try:
        ctrl = ctrl_engine.submit("resume me", max_new_tokens=40, temperature=0.0).result(timeout=300)
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
        fut = engine.submit("resume me", max_new_tokens=40, temperature=0.0, stream_cb=lambda t, s, d: got.append(t))
        low = fut.result(timeout=300)
        timeline = engine.timeline.get(fut.request_id)
    finally:
        engine.stop()
    assert preempted.is_set() and any(p.startswith("preempted") for p in timeline.phases)
    assert low.token_ids == ctrl.token_ids and [t for t in got if t >= 0] == list(ctrl.token_ids)  # -1: the stream's end


@pytest.mark.parametrize("name", ["lfm2_moe", "nemotron_h"])
def test_the_families_lower_the_programs_they_lowered_before(name):
    """A family's own lowering (``lowered_programs`` at tiny widths: the
    bucketed prefill, the decode block, the ragged dispatch) hashes as
    recorded: ``lfm2_moe``'s as on the tree before this model came — the
    relu² branch of the expert ops and the engine's scan-position
    attribute reach no SwiGLU family's programs (the other families' are
    held to the same tree by ``tests/test_lfm2_moe.py`` and
    ``tests/test_joyai_flash.py``, and read the same here: ``PARENT_DIGESTS``)
    — and ``nemotron_h``'s own (``tests/lowered_digests.py``)."""
    recorded = NEMOTRON_DIGESTS if name == "nemotron_h" else PARENT_DIGESTS[name]
    assert lowered_digests.digests(name) == recorded


PARENT_DIGESTS = {
    "llama-gqa": {
        "decode_block_paged": "a8ffccbc4b758d14e1521b0836a2acbd85ef689e03f3b30d74f147224320aeaa",
        "prefill_compute[16]": "8a05ab1047796690fa550a1c037040f07cda5c81ba45be39160b0526b5929bbd",
        "ragged_step_paged": "6f77b48a7ca8cb6927a9784cb5f296abc620e1f061be145acdd14d49722ef4b9",
    },
    "llama-mha": {
        "decode_block_paged": "1e851bfd50c2e79e9af22d192cdc18d4a1249fcee38cb2936859d52678a9d708",
        "prefill_compute[16]": "086f3498a06fd774c1fb186fedb326de02ef27d4d2fa4dcdf29f4d462071e4a7",
        "ragged_step_paged": "c38bb4c97a0d968d6606e6536e58470c9a58554e9448fcb6fca90c2914489237",
    },
    "cohere2_moe": {
        "decode_block_paged": "d3690c7de2af9d5501f7b4dab14a2e7f9cffb2df2f89a21c59fb57c83296e63a",
        "prefill_compute[16]": "a353abd5f2a96d8263d35d6a244bc544a706030addd968f8d43d443a5e0ed0da",
        "ragged_step_paged": "868144e2d986967ad17e426b2efb591d47b16e00bff46208fd6304f1ef76cd4c",
    },
    "deepseek_v32": {
        "decode_block_paged": "a24fb12cd044b30edb5bc144f4db43436f82f4575e604fe121c6ff4b324e067c",
        "prefill_compute[16]": "ff812d940241006b1d3abdce7e42117757fed229debf7f65dd9f6d0b895fdbc8",
        "ragged_step_paged": "46d841c52ce3aacf79a7a2046f2d09f0963e9be31553e61b7c3c137200453611",
    },
    "phi4flash": {
        "decode_block_paged": "b31b4902300969008dd84b598fa624bc75e22e36fc3b8ace7f560ec16f56fa9a",
        "prefill_compute[16]": "0bbdd11424c9858187e87e8e2bc6731e762108a3ac822a38a5f47632fd23ac4b",
        "ragged_step_paged": "2280d852d4c2e11df70b0c0808d7806acad020e5098665877e29726428cf2b86",
    },
    "joyai_llm_flash": {
        "decode_block_paged": "8d5ea1972633b5f37cb4f6d9adb560290679ece923b217e0ca2d62486ba32a12",
        "prefill_compute[16]": "a1197d3f284ffacb8e9076469d5e88b30d359b39e1557dfae86c50e8dd4c834e",
        "ragged_step_paged": "cfdee0e4a6c87dc4d9189b21c7ffa3f00f54eba64ba9c3dda97f5cd25a4f9d38",
    },
    "lfm2_moe": {
        "decode_block_paged": "18ed9481dd5c1e0e0741cb337d119ea93aa0a198e6c84f56249f8b10da96139e",
        "prefill_compute[16]": "0bf8ec80d1ebb0f48293cf8cdab3067b51102b3133cdc9d8e990caf8f4ca96d8",
        "ragged_step_paged": "0985ae8ae16565787ad6a73c43873b26051212b2e372c194021427b0708d6839",
    },
}
NEMOTRON_DIGESTS = {
    "decode_block_paged": "3121ba9a4512579472f22d63256a8f8f987aba82c54b93e72f36cb09617b040e",
    "prefill_compute[16]": "6dc5147d363bc9be102d1baf114a2c2e8bb9fd480d69de203a0203989675d432",
    "ragged_step_paged": "ef8a0ae7b6033cd2290a5e7ee3f9bba2d08d23fbf72b176a074adcfa86215b81",
}
