"""``models/lfm2_moe.py`` against its plain reference
(``benchmarks/harness/lfm2_moe_reference.py``: float32, every layer over
every position, the conv a literal sum over the sequence with no tail
carried, attention unpaged, every expert dense under its gate, nothing
imported from the program) at tiny widths with the published structure
on the CPU: 9 layers — two dense conv layers, a period of four (attention,
three conv) and a last period of three — hidden 64, 4 / 2 heads of 16,
8 experts of 32, top 4 with a seeded expert bias, conv kernel 3, pages of
4 — seeded weights.

Tolerances, each with its reason:

- ``TOL`` 2e-3 on logits of deviation about 1: the program in float32
  differs from the reference by the order of sums only (readings 1e-6 to
  1e-5 through prefill, chunks and decode); the int8 tree by the same (both
  de-quantise the same integers). The int4 control and a router rounded to
  bfloat16 both fail it, each by more than twice: rounded, some rows choose
  another expert, and a changed choice moves a logit by a step.
"""

import json
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lowered_digests
from benchmarks.harness import lfm2_moe_reference as reference
from gofr_tpu.models import lfm2_moe as lm
from gofr_tpu.ops import moe, ssm
from gofr_tpu.serving import ByteTokenizer, EngineConfig, ServingEngine
from gofr_tpu.serving import batch as batch_ops
from gofr_tpu.serving.kv_cache import PagedKVCache

CFG = lm.Lfm2MoeConfig.tiny(vocab_size=300)
PAGE, TOL = 4, 2e-3


def as_file(cfg):
    """The program's config under the published keys the reference reads."""
    return {"hidden_size": cfg.d_model, "num_hidden_layers": cfg.n_layers, "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "layer_types": list(cfg.layer_types),
            "num_dense_layers": cfg.n_dense_layers, "num_experts": cfg.n_experts, "num_experts_per_tok": cfg.top_k,
            "norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta, "routed_scaling_factor": 1,
            "conv_L_cache": cfg.conv_kernel, "vocab_size": cfg.vocab_size}


@pytest.fixture(scope="module")
def plain():
    return lm.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def int8(plain):
    return lm.quantize_params(plain)


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(7).integers(0, CFG.vocab_size, 200).astype(np.int32)


@pytest.fixture(scope="module")
def want(plain, ids):
    return np.asarray(reference.logits(as_file(CFG), plain, ids[:120]))


def pager(cfg=CFG, slots=2, max_seq=128):
    return PagedKVCache(cfg, num_pages=slots * max_seq // PAGE, page_size=PAGE, max_slots=slots,
                        max_seq_len=max_seq, spec=lm.cache_spec(cfg, PAGE))


def bucketed(cfg, params, pc, slot, prompt, bucket, seq_id=1):
    """Prefill ``prompt`` in a bucket and write it into ``slot``; the last position's logits."""
    tokens = np.zeros((1, bucket), np.int32)
    tokens[0, :len(prompt)] = prompt
    last, cache = lm.prefill(cfg, params, jnp.asarray(tokens), lm.KVCache.create(cfg, 1, bucket),
                             jnp.asarray([len(prompt)]))
    pc.alloc_slot(slot, seq_id=seq_id, prompt_len=len(prompt), reserve_tokens=bucket)
    pc.write_prefill(slot, *lm.prefill_slabs(cache))
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
        logits, pc.k_pool, pc.v_pool = lm.decode_chunk_paged(
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
        logits, pc.k_pool, pc.v_pool, counted = lm.decode_step_paged(
            cfg, params, jnp.asarray(tok), pc.k_pool, pc.v_pool, pc.tables_device(), jnp.asarray(lens),
            jnp.asarray(active))
        pc.advance_slot(slot, 1)
        out.append(np.asarray(logits[slot]))
        stats = stats + np.asarray(counted)
    return np.stack(out), stats


# ------------------------------------------------------ hand values, the ops
def test_the_layer_map_is_the_published_one():
    full = lm.Lfm2MoeConfig()
    attn = [l for l, kind in enumerate(full.layer_types) if kind == lm.ATTN]
    assert attn == [2, 6, 10, 14, 18, 21] and (full.n_conv, full.n_attn, full.n_layers) == (18, 6, 24)
    first, n_conv = lm._blocks(full)
    assert first.tolist() == attn and n_conv.tolist() == [3, 3, 3, 3, 2, 2]  # the last period is three layers
    assert first.tolist()[0] == full.n_dense_layers == 2 and 2 + 6 + sum(n_conv) == 24
    assert (full.held_experts, full.first_expert, lm.step_stats_len(full)) == (32, 0, 35)
    assert lm.cache_spec(full, 16) == ((("full", 6, (4, 16, 128), (4, 16, 128), None),), {"conv": (18, (2, 2048), jnp.float32)})
    assert full.kv_heads == (4, 128)  # two KV heads of 64 a cached head: a whole lane tile
    mapped = reference.layer_map({"layer_types": list(full.layer_types), "num_dense_layers": 2})
    assert [m[0] for m in mapped] == list(full.layer_types)
    assert [m[1] for m in mapped if m[0] == "conv"] == list(range(18)) and [m[3] for m in mapped[2:]] == list(range(22))
    assert lm._blocks(CFG)[1].tolist() == [3, 2]
    with pytest.raises(ValueError, match="before the first attention layer"):
        lm.Lfm2MoeConfig.tiny(n_dense_layers=3)


def test_the_short_conv_is_the_literal_sum_and_carries_its_tail():
    """No bias, no activation: z_t = sum_k c_k v_{t-2+k}, v = 0 before the
    sequence; split at every offset mod 3, the tails carry it exactly."""
    B, T, D, K = 2, 10, 6, 3
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    v, w = jax.random.normal(ks[0], (B, T, D)), jax.random.normal(ks[1], (K, D))
    padded = jnp.concatenate([jnp.zeros((B, K - 1, D)), v], axis=1)
    literal = sum(w[k] * padded[:, k:k + T] for k in range(K))
    zero = jnp.zeros((B, K - 1, D))
    whole, _ = ssm.causal_conv(v, zero, w, silu=False)
    assert float(jnp.max(jnp.abs(whole - literal))) < 1e-6
    for cut in (1, 2, 3, 4, 5):
        first, seen = ssm.causal_conv(v[:, :cut], zero, w, silu=False)
        second, _ = ssm.causal_conv(v[:, cut:], ssm.conv_tail(seen, jnp.full((B,), cut), K - 1), w, silu=False)
        assert float(jnp.max(jnp.abs(jnp.concatenate([first, second], 1) - literal))) < 1e-6


def test_the_expert_bias_moves_the_choice_and_not_the_gate():
    """``sigmoid_topk_gates`` with the bias: the chosen experts are the
    top 4 of s + e_bias (another set than s alone gives on some rows), and
    each gate is the UNcorrected score over the chosen ones' sum — the
    reference's rule (its 1e-6 in the sum moves a gate by under 1e-5 here)."""
    T, D, E = 64, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    h, w = jax.random.normal(ks[0], (T, D)), jax.random.normal(ks[1], (D, E)) / 4
    bias = 0.1 * jax.random.normal(ks[2], (E,))
    gates = np.asarray(moe.sigmoid_topk_gates(h, w, 4, bias=bias))
    plain = np.asarray(moe.sigmoid_topk_gates(h, w, 4))
    s = np.asarray(jax.nn.sigmoid(jnp.matmul(h, w, precision=jax.lax.Precision.HIGHEST)))
    chosen = np.argsort(-(s + np.asarray(bias)), axis=1)[:, :4]
    assert ((gates > 0).sum(1) == 4).all() and all(set(np.nonzero(g)[0]) == set(c) for g, c in zip(gates, chosen))
    assert ((gates > 0) != (plain > 0)).any(axis=1).sum() >= 4  # the bias changed some rows' choice
    picked = np.take_along_axis(s, chosen, 1)
    want = picked / (picked.sum(1, keepdims=True) + 1e-6)
    assert np.abs(np.take_along_axis(gates, chosen, 1) - want).max() < 1e-5


# ------------------------------------------------- the model and the reference
@pytest.mark.parametrize("weights", ["plain", "int8"])
def test_bucketed_prefill_then_paged_decode_is_the_reference_at_every_position(weights, ids, request):
    """A prompt of 11 in a bucket of 16, then 109 decode steps through the
    paged pool: the reference's logits at every position served; the
    counters are what ran — 7 conv layers and 2 attention layers a step."""
    params = request.getfixturevalue(weights)
    want = np.asarray(reference.logits(as_file(CFG), params, ids[:120]))
    pc = pager()
    assert np.abs(bucketed(CFG, params, pc, 1, ids[:11], 16) - want[10]).max() < TOL
    got, stats = decode(CFG, params, pc, 1, ids[11:120])
    assert np.abs(got - want[11:120]).max() < TOL
    E = CFG.n_experts
    conv_rows, attn_kv = stats[E + 1:].tolist()
    assert conv_rows == CFG.n_conv * 109 and attn_kv == CFG.n_attn * sum(range(12, 121))
    assert stats[:E].sum() == 109 * CFG.top_k * (CFG.n_layers - CFG.n_dense_layers)
    assert stats[E] == stats[:E].sum()  # one row: its own 4 experts a layer are read, on the own-rows path
    pc.close()


@pytest.mark.parametrize("chunk", [4, 5], ids=["chunk4", "chunk5"])
def test_chunked_prefill_carries_the_tails_across_every_offset(plain, ids, want, chunk):
    """A prompt of 23 through chunks of 4 (boundaries at 4, 8, ..., 20:
    every offset mod 3) or 5, then decode: the reference at every position
    served. The slot's last occupant left tails; a chunk at 0 zeroes them,
    and the other slot's are not touched."""
    pc = pager()
    pc.k_pool["state"] = jax.tree.map(lambda a: a + 5.0, pc.k_pool["state"])
    logits = chunked(CFG, plain, pc, 1, ids[:23], chunk)
    assert {b % 3 for b in range(chunk, 23, chunk)} == {0, 1, 2}
    assert len(logits) == -(-23 // chunk) and np.abs(logits[-1] - want[22]).max() < TOL
    for n, got in zip(range(chunk, 23, chunk), logits):  # each chunk's head at its last position
        assert np.abs(got - want[n - 1]).max() < TOL
    assert bool(jnp.all(pc.k_pool["state"]["conv"][:, 0] == 5.0))
    got, _ = decode(CFG, plain, pc, 1, ids[23:60])
    assert np.abs(got - want[23:60]).max() < TOL
    pc.close()


def test_a_buckets_padding_leaves_the_tails_where_seq_len_put_them(plain, ids):
    out = []
    for bucket in (12, 16, 32):
        tokens = np.full((1, bucket), 9, np.int32)
        tokens[0, :11] = ids[:11]
        last, cache = lm.prefill(CFG, plain, jnp.asarray(tokens), lm.KVCache.create(CFG, 1, bucket), jnp.asarray([11]))
        out.append((np.asarray(last), np.asarray(cache.k["state"]["conv"])))
    for last, tails in out[1:]:
        assert np.abs(last - out[0][0]).max() < 1e-5 and np.abs(tails - out[0][1]).max() < 1e-6


def test_a_slot_is_reused_after_a_longer_occupant(plain, ids, want):
    pc = pager()
    bucketed(CFG, plain, pc, 0, ids[100:131], 32, seq_id=1)
    decode(CFG, plain, pc, 0, ids[131:180])
    pc.free_slot(0)
    assert np.abs(bucketed(CFG, plain, pc, 0, ids[:6], 16, seq_id=2) - want[5]).max() < TOL
    assert np.abs(decode(CFG, plain, pc, 0, ids[6:40])[0] - want[6:40]).max() < TOL
    pc.close()


def test_a_done_or_idle_rows_tails_are_not_advanced_by_a_block(plain, ids):
    """``decode_block_paged`` over three slots: slot 0 live, slot 1 done
    (its budget spent), slot 2 never dispatched. Only slot 0's tails move;
    the counters count its steps alone — ``conv_rows`` is the conv layers
    times the live row-steps, so no attention layer ran a conv."""
    pc = pager(slots=3)
    for slot in range(3):
        bucketed(CFG, plain, pc, slot, ids[slot * 20:slot * 20 + 9], 16, seq_id=slot + 1)
        assert pc.try_reserve_slot(slot, 4)
    before = np.asarray(pc.k_pool["state"]["conv"])
    n = np.full(3, 9, np.int32)
    state = batch_ops.make_decode_state(ids[[9, 29, 49]], n, [False, True, False], [50, 0, 50], [-1] * 3, [0.0] * 3,
                                        [0] * 3, [1.0] * 3, jax.random.PRNGKey(0))
    packed, pc.k_pool, pc.v_pool, state = batch_ops.decode_block_paged(
        CFG, plain, pc.k_pool, pc.v_pool, state, pc.tables_device(), jnp.asarray([True, True, False]), 4)
    after = np.asarray(pc.k_pool["state"]["conv"])
    assert (after[:, 1:] == before[:, 1:]).all() and (after[:, 0] != before[:, 0]).any()
    stats = batch_ops.block_stats(np.asarray(packed), 3, lm.step_stats_len(CFG))
    E = CFG.n_experts
    assert dict(zip(lm.STEP_STATS, stats[E + 1:].tolist())) == {
        "conv_rows": CFG.n_conv * 4, "attn_kv": CFG.n_attn * sum([10, 11, 12, 13])}
    assert stats[:E].sum() == 4 * CFG.top_k * (CFG.n_layers - CFG.n_dense_layers)
    pc.close()


def test_the_int4_control_and_a_bfloat16_router_fail_the_same_tolerance(plain, int8, ids):
    file = as_file(CFG)
    want8 = np.asarray(reference.logits(file, int8, ids[:64]))
    control = np.asarray(reference.logits(file, int8, ids[:64], weight_bits=4))
    assert np.abs(control - want8).max() > 2 * TOL
    want = np.asarray(reference.logits(file, plain, ids[:64]))
    rounded = dict(plain, moe=dict(plain["moe"], w_router=plain["moe"]["w_router"].astype(jnp.bfloat16)))
    pc = pager()
    first = bucketed(CFG, rounded, pc, 0, ids[:11], 16)
    got, _ = decode(CFG, rounded, pc, 0, ids[11:64])
    assert max(np.abs(first - want[10]).max(), np.abs(got - want[11:64]).max()) > 2 * TOL
    pc.close()


def test_the_five_families_lower_the_programs_they_lowered_before():
    """Each served family's own lowering (``lowered_programs`` at tiny
    widths: the bucketed prefill, the decode block, the ragged dispatch)
    hashes as it did on the tree before this model came (PR 38's): the
    conv op grew neutral arguments and the engine's counter split grew a
    case, and neither reaches another family's programs. A PR that changes
    them on purpose records them again (``tests/lowered_digests.py``)."""
    for name, recorded in PARENT_DIGESTS.items():
        assert lowered_digests.digests(name) == recorded, name


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
], ids=["speculative", "lora", "prefix_cache", "spill", "role"])
def test_engines_the_model_has_no_program_for_are_refused_at_construction(plain, settings, lora, sentence):
    with pytest.raises(ValueError, match=sentence):
        ServingEngine(CFG, plain, engine_settings(**settings), ByteTokenizer(300), lora=lora)


def test_the_seam_finds_the_module_its_store_and_its_counters():
    assert batch_ops.model_of(CFG) is lm and lm.step_stats_len(CFG) == CFG.n_experts + 3
    assert lm.unserved(engine_settings(), None, CFG) is None and not hasattr(lm, "CHUNK_TAKES_FINISH")
    pc = pager()
    assert set(pc.k_pool) == {"full", "state"} and set(pc.v_pool) == {"full"}
    assert pc.k_pool["full"].shape == (CFG.n_attn, 2 * 128 // PAGE + 1, CFG.n_kv_heads // 2, PAGE, 2 * CFG.head_dim)
    assert pc.k_pool["state"]["conv"].shape == (CFG.n_conv, 2, 2, CFG.d_model)
    assert pc.k_pool["state"]["conv"].dtype == jnp.float32 and pc.ring_pools == ()
    pc.close()


def test_the_model_is_served_behind_an_app_over_http_with_its_spans_and_counters(plain, monkeypatch):
    """POST /generate and the SSE route through a real App, a bucketed and
    a chunked prompt, 40 tokens each: the tokens are the reference's greedy
    choice; the commit spans carry ``conv_rows`` (the conv layers times the
    live row-steps: no layer ran the other kind's mixer), ``attn_kv`` and
    the experts' ``moe_rows``, ``moe_max`` and ``moe_reached``; every
    block's dispatch span names the branch of ``held_experts`` its rows
    take (``moe_path``: at three slots and 8 experts a row chooses 4 of,
    the grouped product); /metrics counts the experts' rows and reads and
    the blocks by that branch."""
    import gofr_tpu
    from gofr_tpu.config import MapConfig
    from gofr_tpu.serving import engine as engine_mod
    from gofr_tpu.serving.handlers import register_generation_routes
    from gofr_tpu.testutil import get_free_port

    http_port, metrics_port = get_free_port(), get_free_port()
    app = gofr_tpu.App(MapConfig({"HTTP_PORT": str(http_port), "METRICS_PORT": str(metrics_port),
                                  "APP_NAME": "lfm2-test", "LOG_LEVEL": "WARN"}, use_env=False))
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
    commits = [kw for phase, kw in seen if phase == "commit" and "conv_rows" in kw]
    live = [kw["conv_rows"] // CFG.n_conv for kw in commits]
    assert sum(live) >= 4 * 40 - 8 and all(kw["conv_rows"] == CFG.n_conv * n for kw, n in zip(commits, live))
    expert_layers = CFG.n_layers - CFG.n_dense_layers
    assert all(kw["moe_rows"] == CFG.top_k * expert_layers * n for kw, n in zip(commits, live))
    assert all(kw["moe_reached"] <= kw["moe_rows"] and kw["moe_max"] <= expert_layers * n for kw, n in zip(commits, live))
    # one request at a time: a live step reads its whole context in each attention layer
    assert all(kw["attn_kv"] >= CFG.n_attn * n for kw, n in zip(commits, live)) and any(kw["attn_kv"] for kw in commits)
    rows = sum(int(float(l.rsplit(" ", 1)[1])) for l in metrics.splitlines() if l.startswith("app_moe_expert_rows_total{"))
    assert rows == sum(kw["moe_rows"] for kw in commits) > 0
    assert any(l.startswith("app_moe_experts_read_total ") for l in metrics.splitlines())
    paths = [kw["moe_path"] for phase, kw in seen if phase == "dispatch" and "moe_path" in kw]
    blocks = [kw for phase, kw in seen if phase == "dispatch" and "blk" in kw]
    assert paths == ["grouped"] * len(blocks) and moe.path(3, CFG.n_experts, CFG.top_k, plain["moe"]["experts"]) == "grouped"
    counted = {l.split("{", 1)[1].split("}")[0]: int(float(l.rsplit(" ", 1)[1]))
               for l in metrics.splitlines() if l.startswith("app_moe_path_blocks_total{")}
    assert counted == {'path="grouped"': len(blocks)} and blocks
    pages = health["data"]["details"]["serving"]["details"]["kv_pages"]
    assert pages["pools"] == {"full": {"used": 0, "total": 3 * 96 // PAGE}}


def test_a_preempted_request_resumes_by_prefilling_what_it_emitted(plain):
    """No snapshot of the tails is kept: a preempted row is requeued and
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
    gaps = reference.served_gaps(as_file(CFG), plain, tokenizer.encode("resume me"), list(low.token_ids))["served"]
    assert gaps.max() < TOL


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
        # recorded again: the chunk's indexer reads the context that holds its end, not the slot
        "ragged_step_paged": "46d841c52ce3aacf79a7a2046f2d09f0963e9be31553e61b7c3c137200453611",
    },
    "phi4flash": {
        "decode_block_paged": "b31b4902300969008dd84b598fa624bc75e22e36fc3b8ace7f560ec16f56fa9a",
        "prefill_compute[16]": "0bbdd11424c9858187e87e8e2bc6731e762108a3ac822a38a5f47632fd23ac4b",
        "ragged_step_paged": "2280d852d4c2e11df70b0c0808d7806acad020e5098665877e29726428cf2b86",
    },
}
