"""``models/cohere2_moe.py`` on the serving path, at small widths with
seeded random weights (hidden 64, 8 query / 2 KV heads of 16, window 8, 16
experts with 4 a token and 2 shared, two periods of four layers), against
the plain reference the benchmark decides ``correct`` with
(``benchmarks/harness/cohere2_moe_reference.py``: float32, ``highest``,
nothing of the program).

The tolerance of the logit comparisons, ``TOL`` = 2e-3: program and
reference compute the same float32 mathematics in another order (a scan
over stacked layers and an expert loop against one layer and one expert at
a time; logits have deviation 8 and reach 40 at these widths), which reads
under 3e-4 here. One step of lower precision — the same weights in int4 —
moves logits by more than 0.1 and fails it.
"""

import json
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import cohere2_moe_reference as reference
from gofr_tpu.models import cohere2_moe as cm
from gofr_tpu.ops import moe as moe_ops
from gofr_tpu.ops.norms import layer_norm
from gofr_tpu.ops.paged_attention import paged_decode_attention, paged_decode_attention_ref
from gofr_tpu.ops.rope import apply_rope_interleaved, rope_angles
from gofr_tpu.serving import ByteTokenizer, EngineConfig, ServingEngine
from gofr_tpu.serving import batch as batch_ops

TOL = 2e-3
PAGE = 4
CFG = cm.Cohere2MoeConfig.tiny(vocab_size=300)


def as_file(cfg, first=0):
    """The configuration file's keys for a program config: what the
    reference reads."""
    return {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "num_experts_per_tok": cfg.top_k, "layer_types": list(cfg.layer_types),
        "sliding_window": cfg.sliding_window, "rope_theta": cfg.rope_theta,
        "layer_norm_eps": cfg.norm_eps, "logit_scale": cfg.logit_scale,
        "deployment": {"first_expert": first},
    }


@pytest.fixture(scope="module")
def plain():
    return cm.init_params(CFG, jax.random.PRNGKey(7))


@pytest.fixture(scope="module")
def int8(plain):
    return cm.quantize_params(plain)


def hold_share(params, first, held, vocab_rows):
    """The share of a whole tree that one chip holds: the routed experts
    ``first .. first + held`` of every layer, the shared experts, attention
    and router whole, and a slice of the tied embedding."""
    lp = dict(params["layers"])
    lp["experts"] = jax.tree.map(lambda a: a[:, first:first + held], lp["experts"])
    return dict(params, layers=lp, embedding=params["embedding"][vocab_rows])


def ids_of(n, seed=3):
    return np.asarray([1] + list(np.random.default_rng(seed).integers(3, 259, n - 1)), np.int32)


def paged(cfg, slots, pages_per_slot):
    """Empty pools and block tables that give every slot its own pages,
    in an order that is not the identity."""
    n = slots * pages_per_slot
    shape = (cfg.n_layers, n + 1, cfg.n_kv_heads, PAGE, cfg.head_dim)
    tables = np.random.default_rng(1).permutation(n).reshape(slots, pages_per_slot).astype(np.int32)
    return jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype), jnp.asarray(tables)


def write_slab(pool, slab, table, start=0):
    """A prefill slab [L, S, Hkv, Dh] into a row's pages from ``start``."""
    for t in range(slab.shape[1]):
        pos = start + t
        pool = pool.at[:, table[pos // PAGE], :, pos % PAGE].set(slab[:, t])
    return pool


def serve_through_the_cache(cfg, params, ids, n_prompt, bucket):
    """Bucketed prefill of the first ``n_prompt`` tokens, then the rest one
    decode step at a time through the paged pool (teacher-forced): the
    logits at positions n_prompt-1 .. len(ids)-1."""
    tokens = np.zeros((1, bucket), np.int32)
    tokens[0, :n_prompt] = ids[:n_prompt]
    last, k_slab, v_slab = batch_ops.prefill_compute(cfg, params, jnp.asarray(tokens), jnp.asarray([n_prompt]))
    kp, vp, tables = paged(cfg, 2, 16)
    kp, vp = write_slab(kp, k_slab[:, :n_prompt], tables[0]), write_slab(vp, v_slab[:, :n_prompt], tables[0])
    out, counted = [np.asarray(last[0])], []
    for pos in range(n_prompt, len(ids)):
        logits, kp, vp, rows = cm.decode_step_paged(
            cfg, params, jnp.asarray([ids[pos], 0]), kp, vp, tables,
            jnp.asarray([pos + 1, 1]), jnp.asarray([True, False]))
        out.append(np.asarray(logits[0]))
        counted.append(np.asarray(rows))
    return np.stack(out), np.stack(counted)


def chunked(cfg, params, ids, chunk):
    """The whole sequence through ``decode_chunk_paged``, ``chunk`` tokens
    a dispatch: logits at every position."""
    kp, vp, tables = paged(cfg, 2, 16)
    out = []
    for start in range(0, len(ids), chunk):
        piece = np.full((2, chunk), -1, np.int32)
        n = min(chunk, len(ids) - start)
        piece[0, :n] = ids[start:start + n]
        logits, kp, vp = cm.decode_chunk_paged(
            cfg, params, jnp.asarray(piece), kp, vp, tables, jnp.asarray([start, 0]),
            jnp.asarray([True, False]), jnp.asarray([64, 0]))
        out.append(np.asarray(logits[0, :n]))
    return np.concatenate(out)


# ------------------------------------------- (a), (b): against the reference
@pytest.mark.parametrize("weights", ["plain", "int8"])
def test_prefill_then_decode_past_the_window_agrees_with_the_reference(weights, request):
    params = request.getfixturevalue(weights)
    ids = ids_of(40)
    want = np.asarray(reference.logits(as_file(CFG), params, ids))
    got, counted = serve_through_the_cache(CFG, params, ids, n_prompt=12, bucket=16)
    # 28 decoded positions, the last at 39: the window of 8 binds from position 8 on
    assert got.shape == (29, 300) and np.abs(want).max() > 10
    assert np.abs(got - want[11:]).max() < TOL
    # every step, each of 8 layers routes the one live row to 4 of the 16 experts, all held
    # (2 rows x 4 of 16 experts: the product groups the rows and counts, last, the experts it read — the live row's)
    assert counted.shape == (28, 17) and (counted[:, :16].sum(axis=1) == 8 * 4).all() and (counted[:, 16] == 8 * 4).all()


@pytest.mark.parametrize("chunk", [12, 3], ids=["chunks-of-12-the-loop-over-every-row", "chunks-of-3-the-grouped-product"])
@pytest.mark.parametrize("weights", ["plain", "int8"])
def test_chunked_prefill_agrees_with_the_reference(weights, chunk, request):
    params = request.getfixturevalue(weights)
    ids = ids_of(40, seed=4)
    want = np.asarray(reference.logits(as_file(CFG), params, ids))
    # two rows a dispatch: 24 tokens x 4 of 16 experts is 6 rows an expert, the loop; 6 tokens is 1.5, grouped
    assert moe_ops.groups_rows(2 * chunk, CFG.n_experts, CFG.top_k) is (chunk == 3)
    got = chunked(CFG, params, ids, chunk)  # chunks at 0, 12, 24, 36 (or every 3): the window crosses them
    assert np.abs(got - want).max() < TOL


def test_the_int4_control_fails_the_same_tolerance(int8):
    ids = ids_of(40)
    got, _ = serve_through_the_cache(CFG, int8, ids, n_prompt=12, bucket=16)
    control = np.asarray(reference.logits(as_file(CFG), int8, ids, weight_bits=4))
    assert np.abs(got - control[11:]).max() > 50 * TOL


def test_a_window_that_never_binds_is_full_attention_with_rope(plain):
    """The window is what separates the two: with a window wider than the
    sequence the sliding layers see every key, and the logits move."""
    ids = ids_of(24)
    wide = cm.Cohere2MoeConfig.tiny(vocab_size=300, sliding_window=64)
    a, _ = serve_through_the_cache(CFG, plain, ids, 12, 16)
    b, _ = serve_through_the_cache(wide, plain, ids, 12, 16)
    assert np.abs(a[:1] - b[:1]).max() > 10 * TOL  # position 11 already looks past 8 keys
    assert np.abs(b - np.asarray(reference.logits(as_file(wide), plain, ids))[11:]).max() < TOL


# ------------------------------------------------------------ (c): the share
def test_the_shares_add_up_to_the_uncut_layer(plain):
    """Four chips hold 4 of the 16 experts each: their parts, with the
    shared experts counted once, are the whole layer's routed + shared
    sum, as the reference computes it uncut."""
    lp = jax.tree.map(lambda a: a[2], plain["layers"])
    h = jax.random.normal(jax.random.PRNGKey(5), (24, CFG.d_model), jnp.float32)
    gates = moe_ops.sigmoid_topk_gates(h, lp["w_router"], CFG.top_k)
    assert ((gates > 0).sum(axis=1) == CFG.top_k).all() and np.allclose(gates.sum(axis=1), 1.0, atol=1e-6)
    none_held = jax.tree.map(lambda a: a[:0], lp["experts"])
    shared, *_ = moe_ops.held_experts(h, gates, none_held, lp["shared"], 0)
    total, counted = jnp.zeros_like(shared), []
    for first in (0, 4, 8, 12):
        share = jax.tree.map(lambda a: a[first:first + 4], lp["experts"])
        part, g, _ = moe_ops.held_experts(h, gates, share, lp["shared"], first)
        total += part - shared
        counted.append(int((g > 0).sum()))
    whole, *_ = moe_ops.held_experts(h, gates, lp["experts"], lp["shared"], 0)
    assert sum(counted) == 24 * CFG.top_k
    assert np.abs(total + shared - whole).max() < 1e-5
    uncut = reference._ffn_sum(h, lp["experts"], gates.T, 8) + reference._ffn_sum(
        h, lp["shared"], jnp.full((CFG.n_shared, 24), 1.0 / CFG.n_shared), 8)
    assert np.abs(whole - uncut).max() < 1e-4 and np.abs(uncut).max() > 0.1


def test_a_share_of_the_model_is_the_reference_given_the_same_share(plain):
    """Experts 8..11 held and rows 0..199 of the vocabulary: program and
    reference leave out the same part, and differ from the whole model."""
    cfg = cm.Cohere2MoeConfig.tiny(vocab_size=200, held_experts=4, first_expert=8)
    share = hold_share(plain, 8, 4, slice(0, 200))
    ids = np.minimum(ids_of(24), 199)
    got, counted = serve_through_the_cache(cfg, share, ids, 12, 16)
    want = np.asarray(reference.logits(as_file(cfg, first=8), share, ids))
    assert got.shape[1] == 200 and np.abs(got - want[11:]).max() < TOL
    whole = np.asarray(reference.logits(as_file(CFG), plain, ids))[11:, :200]
    assert np.abs(got - whole).max() > 10 * TOL
    # a quarter of the experts: some rows, not all; after them the experts read (2 rows x 4 of 16: grouped)
    assert counted.shape[1] == 4 + 1 and 0 < counted[:, :4].sum() < 12 * 8 * 4
    assert ((counted[:, :4] > 0).sum(axis=1) <= counted[:, 4]).all() and (counted[:, 4] <= counted[:, :4].sum(axis=1)).all()


# ------------------------------------------------ (d): the kernel's window
@pytest.mark.parametrize("window", [1, 5, 16, 17, 40, 128, 129, 500])
def test_paged_kernel_with_a_window_against_its_reference(window):
    """Interpret mode. Page 16, so blocks of 128 tokens: windows whose edge
    falls inside a page (5, 17, 40), on a page's edge (16), on a block's
    (128) and one past it (129); rows shorter than the window, of length 1
    and 0, and longer than two blocks."""
    B, H, Hkv, Dh, page, M = 6, 8, 2, 128, 16, 24
    ks = jax.random.split(jax.random.PRNGKey(window), 3)
    q = jax.random.normal(ks[0], (B, H, Dh), jnp.float32)
    kp = jax.random.normal(ks[1], (B * M + 1, Hkv, page, Dh), jnp.float32)
    vp = jax.random.normal(ks[2], (B * M + 1, Hkv, page, Dh), jnp.float32)
    tables = jnp.asarray(np.random.default_rng(0).permutation(B * M).reshape(B, M), jnp.int32)
    lens = jnp.asarray([1, 37, 200, 384, 131, 0], jnp.int32)
    want = paged_decode_attention_ref(q, kp, vp, tables, lens, window=jnp.int32(window))
    got = paged_decode_attention(q, kp, vp, tables, lens, interpret=True, window=jnp.int32(window))
    live = np.asarray(lens) > 0  # a row of length 0 sees no key: its output is not read
    assert np.abs(np.asarray(got - want))[live].max() < 2e-6
    if window < 131:
        full = paged_decode_attention_ref(q, kp, vp, tables, lens)
        assert np.abs(np.asarray(want - full))[2:5].max() > 1e-3  # the window binds on the long rows


def test_paged_kernel_without_a_window_is_the_kernel_without_the_argument():
    """``window=None`` builds the kernel without the argument: bit for bit
    the output of a window wider than every row, a full-attention layer's."""
    B, H, Hkv, Dh, page, M = 4, 8, 2, 128, 16, 12
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, Dh), jnp.bfloat16)
    kp = jax.random.normal(ks[1], (B * M + 1, Hkv, page, Dh), jnp.bfloat16)
    vp = jax.random.normal(ks[2], (B * M + 1, Hkv, page, Dh), jnp.bfloat16)
    tables = jnp.arange(B * M, dtype=jnp.int32).reshape(B, M)
    lens = jnp.asarray([1, 100, 192, 17], jnp.int32)
    none = paged_decode_attention(q, kp, vp, tables, lens, interpret=True)
    wide = paged_decode_attention(q, kp, vp, tables, lens, interpret=True, window=jnp.int32(cm.NO_WINDOW))
    assert bool(jnp.all(none == wide))


# ------------------------------------------------------ (e): closed forms
def test_interleaved_rope_turns_each_pair_by_its_angle():
    Dh, theta = 8, 50000.0
    x = jnp.tile(jnp.asarray([1.0, 0.0]), Dh // 2).reshape(1, 1, 1, Dh)  # every pair is (1, 0)
    for p in (0, 1, 7, 4096):
        sin, cos = rope_angles(jnp.asarray([[p]]), Dh, theta)
        out = np.asarray(apply_rope_interleaved(x, sin, cos))[0, 0, 0]
        angles = p * theta ** (-np.arange(Dh // 2) / (Dh // 2))
        assert np.allclose(out[0::2], np.cos(angles), atol=1e-5) and np.allclose(out[1::2], np.sin(angles), atol=1e-5)
    # a rotation: the norm of each pair is kept, and a pair (a, b) goes to (a cos - b sin, b cos + a sin)
    y = jnp.arange(8.0).reshape(1, 1, 1, 8)
    sin, cos = rope_angles(jnp.asarray([[3]]), 8, theta)
    out = np.asarray(apply_rope_interleaved(y, sin, cos))[0, 0, 0]
    a = 3.0  # pair 0 turns by 3 radians
    assert np.allclose(out[:2], [0 * np.cos(a) - 1 * np.sin(a), 1 * np.cos(a) + 0 * np.sin(a)], atol=1e-5)
    assert np.allclose(out[0::2] ** 2 + out[1::2] ** 2, np.asarray([1.0, 13.0, 41.0, 85.0]), rtol=1e-5)


def test_weight_only_layer_norm_subtracts_the_mean():
    x = jnp.asarray([[1.0, 2.0, 3.0, 4.0]])
    w = jnp.asarray([1.0, 2.0, 1.0, 0.5])
    want = (np.asarray([-1.5, -0.5, 0.5, 1.5]) / np.sqrt(1.25 + 1e-5)) * np.asarray(w)
    assert np.allclose(layer_norm(x, w, None, 1e-5)[0], want, atol=1e-6)
    assert np.allclose(layer_norm(x + 100.0, w, None, 1e-5)[0], want, atol=1e-4)  # RMSNorm would not


# ------------------------------------------- (f), (g): the engine and the App
def engine_settings(**kw):
    settings = dict(max_slots=3, max_seq_len=64, prefill_buckets=(16,), multi_step=4,
                    kv_layout="paged", kv_page_size=8, prefill_chunk_tokens=16)
    settings.update(kw)
    return EngineConfig(**settings)


@pytest.mark.parametrize("settings, lora, sentence", [
    (dict(kv_layout="dense"), None, "paged KV layout only"),
    (dict(spec_tokens=2, multi_step=None), None, "no speculative verify program"),
    (dict(), object(), "serves no LoRA adapters"),
], ids=["dense", "speculative", "lora"])
def test_engines_the_model_has_no_program_for_are_refused_at_construction(plain, settings, lora, sentence):
    with pytest.raises(ValueError, match=sentence):
        ServingEngine(CFG, plain, engine_settings(**settings), ByteTokenizer(300), lora=lora)


def test_the_seam_is_one_lookup_from_the_config_s_class():
    from gofr_tpu.models import llama

    assert batch_ops.model_of(CFG) is cm and batch_ops.model_of(llama.LlamaConfig.tiny()) is llama
    # 16 held experts' rows and the held experts read
    assert (cm.step_stats_len(CFG), llama.step_stats_len(llama.LlamaConfig.tiny())) == (16 + 1, 0)


def test_a_blocks_counters_ride_its_packed_result():
    packed = jnp.arange(18, dtype=jnp.int32).reshape(3, 6)
    stats = jnp.arange(100, 116, dtype=jnp.int32)
    out = np.asarray(batch_ops._append_stats(packed, stats))
    assert out.shape == (6, 6) and (out[:3] == np.asarray(packed)).all()
    assert (batch_ops.block_stats(out, 3, 16) == np.asarray(stats)).all() and out[5, 4:].sum() == 0
    assert batch_ops._append_stats(packed, jnp.zeros(0, jnp.int32)) is packed


@pytest.mark.parametrize("slots", [3, 8], ids=["three-slots-the-grouped-product", "eight-slots-the-loop-over-every-row"])
def test_the_model_is_served_behind_an_app_over_http_with_its_spans_and_counter(plain, monkeypatch, slots):
    """POST /generate and the SSE route through a real App, a bucketed and
    a chunked prompt: the tokens are the reference's greedy choice, the commit spans carry ``moe_rows``,
    ``moe_max`` and ``moe_reached``, the dispatch spans ``win_rows``, and /metrics counts rows
    by expert and the experts read. A decode step of 3 rows (4 of 16 experts each) groups the rows and
    counts the experts it read; one of 8 rows runs every held expert over every row, and counts them all."""
    import gofr_tpu
    from gofr_tpu.config import MapConfig
    from gofr_tpu.serving import engine as engine_mod
    from gofr_tpu.serving.handlers import register_generation_routes
    from gofr_tpu.testutil import get_free_port

    http_port, metrics_port = get_free_port(), get_free_port()
    app = gofr_tpu.App(MapConfig({"HTTP_PORT": str(http_port), "METRICS_PORT": str(metrics_port),
                                  "APP_NAME": "cohere2-moe-test", "LOG_LEVEL": "WARN"}, use_env=False))
    tokenizer = ByteTokenizer(300)
    engine = ServingEngine(CFG, plain, engine_settings(max_slots=slots), tokenizer,
                           metrics=app.container.metrics_manager, logger=app.container.logger)
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
        short = "a short one"          # bucketed prefill
        long = "a prompt of three chunks, and a tail "  # 38 bytes + BOS: chunked at 16
        answers, texts = {}, {}
        for prompt in (short, long):
            with post("/generate/stream", {"prompt": prompt, "max_tokens": 14, "temperature": 0.0}) as resp:
                frames = [json.loads(line[6:]) for line in resp.read().decode().splitlines()
                          if line.startswith("data: {")]
            answers[prompt] = [f["token"] for f in frames if "token" in f]
            with post("/generate", {"prompt": prompt, "max_tokens": 14, "temperature": 0.0}) as resp:
                texts[prompt] = json.loads(resp.read())["data"]
        metrics = urllib.request.urlopen(f"http://127.0.0.1:{metrics_port}/metrics", timeout=10).read().decode()
    finally:
        app.stop()
        thread.join(timeout=60)

    for prompt, served in answers.items():
        ids = tokenizer.encode(prompt)
        assert len(served) == 14 and len(ids) + 14 > CFG.sliding_window
        gaps = reference.served_gaps(as_file(CFG), plain, ids, served)["served"]
        assert gaps.max() < TOL, (prompt, gaps)
        # the JSON route serves the same greedy tokens
        assert texts[prompt]["usage"]["completion_tokens"] == 14 and texts[prompt]["text"] == tokenizer.decode(served)
    commits = [kw for phase, kw in seen if phase == "commit" and "moe_rows" in kw]
    assert any(kw["moe_rows"] for kw in commits) and all(0 <= kw["moe_max"] <= kw["moe_rows"] for kw in commits)
    # a live row's step routes it to top_k experts in each of 8 layers, all held here
    assert all(kw["moe_rows"] % (8 * CFG.top_k) == 0 for kw in commits)
    if moe_ops.groups_rows(slots, CFG.n_experts, CFG.top_k):
        # one request at a time: a live row's 4 experts a layer are the experts read, and an idle step reads none
        assert all(kw["moe_reached"] == kw["moe_rows"] for kw in commits)
    else:
        assert all(kw["moe_reached"] == 4 * 8 * 16 for kw in commits)  # steps x layers x held, whatever the routing
    read = [line for line in metrics.splitlines() if line.startswith("app_moe_experts_read_total")]
    # /metrics was read while the engine still committed blocks: it holds the count of the commits up to one of them
    assert sum(float(line.rsplit(" ", 1)[1]) for line in read) in np.cumsum([kw["moe_reached"] for kw in commits])[4:]
    wins = [kw["win_rows"] for phase, kw in seen if phase == "dispatch" and "win_rows" in kw]
    assert wins and max(wins) >= 1  # rows decode past the window of 8
    counted = [line for line in metrics.splitlines() if line.startswith("app_moe_expert_rows_total{")]
    assert counted and sum(float(line.rsplit(" ", 1)[1]) for line in counted) == sum(kw["moe_rows"] for kw in commits)
    assert all('expert="' in line for line in counted)
