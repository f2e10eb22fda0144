"""Engine recovery from dispatch failures that commit buffer donation.

Round-4's only on-TPU engine run died with ``Array has been deleted with
shape=int32[32]`` and never recovered: a dispatch that fails AFTER its
donation committed (a transient device error; async error surfacing at a
later sync point) leaves the
engine's persistent KV storage pointing at deleted buffers, and every
subsequent step raises forever. The reference's analogue is panic recovery
keeping the server serving (handler.go:55-113) — one poisoned request/step
must not brick the process.

jax 0.9 deletes donated buffers on CPU too (verified here by
``test_cpu_enforces_donation``), so these tests exercise the real
use-after-donate semantics without TPU hardware.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.models import llama
from gofr_tpu.serving import ByteTokenizer, EngineConfig, ServingEngine
from gofr_tpu.serving import batch as batch_ops


def tiny_cfg(max_seq: int = 64) -> llama.LlamaConfig:
    return llama.LlamaConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, max_seq_len=max_seq,
    )


def make_engine(**cfg_kw) -> ServingEngine:
    cfg = tiny_cfg(cfg_kw.get("max_seq_len", 64))
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    defaults = dict(
        max_slots=2, max_seq_len=64, prefill_buckets=(16,),
        admission_per_step=2, max_queue=16,
    )
    defaults.update(cfg_kw)
    return ServingEngine(
        cfg, params, EngineConfig(**defaults), ByteTokenizer(cfg.vocab_size)
    )


def _delete_leaves(tree) -> None:
    for leaf in jax.tree.leaves(tree):
        if hasattr(leaf, "delete"):
            leaf.delete()


def test_cpu_enforces_donation():
    """The premise of this file: donated buffers ARE deleted on the CPU
    backend, so use-after-donate bugs reproduce without hardware."""
    f = jax.jit(lambda x: x + 1, donate_argnums=0)
    a = jnp.zeros(8, jnp.int32)
    f(a)
    with pytest.raises(RuntimeError, match="deleted"):
        _ = a[0]


@pytest.mark.parametrize("kv_layout, entry, n_donated", [
    ("dense", "decode_block", 1), ("paged", "decode_block_paged", 2),
], ids=["dense", "paged"])
def test_decode_failure_after_donation_recovers(monkeypatch, kv_layout, entry, n_donated):
    """A decode dispatch that deletes its donated cache (dense) or its two
    donated pools (paged) and then raises (transport failure after donation
    committed) fails the in-flight requests but leaves the engine servable:
    the recovery path detects the deleted KV storage and rebuilds it."""
    eng = make_engine(kv_layout=kv_layout, kv_page_size=8, multi_step=2)
    real_block = getattr(batch_ops, entry)
    boom = {"n": 0}

    def wrapper(cfg, params, *args, **kw):
        if boom["n"] == 0:
            boom["n"] += 1
            _delete_leaves(args[:n_donated])
            raise RuntimeError("transient transport failure post-donation")
        return real_block(cfg, params, *args, **kw)

    monkeypatch.setattr(batch_ops, entry, wrapper)
    eng.start()
    try:
        fut = eng.submit("hello world", max_new_tokens=8, temperature=0.0)
        with pytest.raises(RuntimeError, match="transient transport"):
            fut.result(timeout=60)
        assert boom["n"] == 1
        # the engine must have rebuilt the donated-and-deleted storage …
        deadline = time.time() + 30
        while eng._kv_unhealthy() and time.time() < deadline:
            time.sleep(0.01)
        assert not eng._kv_unhealthy()
        # … and still serve
        res = eng.submit("try again", max_new_tokens=4, temperature=0.0).result(
            timeout=60
        )
        assert res.finish_reason in ("stop", "length")
    finally:
        eng.stop()


def test_prefill_failure_after_donation_recovers(monkeypatch):
    """The prefill insert donates the SHARED cache; when it dies post-
    donation the per-request error handling must escalate to full recovery
    (isolated cleanup would leave every later step raising)."""
    eng = make_engine()
    real = batch_ops.insert_slot
    boom = {"n": 0}

    def wrapper(k_cache, v_cache, *args, **kw):
        if boom["n"] == 0:
            boom["n"] += 1
            _delete_leaves((k_cache, v_cache))
            raise RuntimeError("transient transport failure post-donation")
        return real(k_cache, v_cache, *args, **kw)

    monkeypatch.setattr(batch_ops, "insert_slot", wrapper)
    eng.start()
    try:
        fut = eng.submit("doomed", max_new_tokens=4, temperature=0.0)
        with pytest.raises(RuntimeError):
            fut.result(timeout=60)
        res = eng.submit("alive", max_new_tokens=4, temperature=0.0).result(
            timeout=60
        )
        # random-init weights may emit EOS first (filtered from the
        # output), so a served-and-finished result with zero kept
        # tokens is a valid recovery outcome
        assert res.finish_reason in ("stop", "length")
    finally:
        eng.stop()


def test_paged_pool_failure_recovers(monkeypatch):
    """Paged twin: a paged decode dispatch that deletes the donated pools
    and raises must trigger a pool rebuild (PagedKVCache.reset_pools)."""
    eng = make_engine(kv_layout="paged", kv_page_size=8)
    real = batch_ops.decode_block_paged
    boom = {"n": 0}

    def wrapper(cfg, params, k_pool, v_pool, *args, **kw):
        if boom["n"] == 0:
            boom["n"] += 1
            k_pool.delete()
            v_pool.delete()
            raise RuntimeError("transient transport failure post-donation")
        return real(cfg, params, k_pool, v_pool, *args, **kw)

    monkeypatch.setattr(batch_ops, "decode_block_paged", wrapper)
    eng.start()
    try:
        fut = eng.submit("doomed", max_new_tokens=8, temperature=0.0)
        with pytest.raises(RuntimeError):
            fut.result(timeout=60)
        res = eng.submit("alive", max_new_tokens=4, temperature=0.0).result(
            timeout=60
        )
        # random-init weights may emit EOS first (filtered from the
        # output), so a served-and-finished result with zero kept
        # tokens is a valid recovery outcome
        assert res.finish_reason in ("stop", "length")
        assert not eng.paged_cache.k_pool.is_deleted()
    finally:
        eng.stop()


def test_block_output_survives_donated_carry_redispatch():
    """Regression pin for the round-4 crash shape ("Array has been deleted
    with shape=int32[32]"): the packed block output the host reads must be
    a DISTINCT buffer from the donated DecodeState carries. Dispatching
    block k+1 — which donates the carry that produced block k's output —
    must leave block k's packed result readable. CPU jax enforces donation
    (test_cpu_enforces_donation), so an aliasing regression raises here
    without TPU hardware."""
    cfg = tiny_cfg(32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    cache = llama.KVCache.create(cfg, 2, max_len=32)
    state = batch_ops.make_decode_state(
        np.array([5, 7], np.int32), np.array([4, 4], np.int32),
        np.array([False, False]), np.array([8, 8], np.int32),
        np.array([-1, -1], np.int32), np.ones(2, np.float32),
        np.zeros(2, np.int32), np.ones(2, np.float32),
        jax.random.PRNGKey(1),
    )
    active = jnp.ones(2, bool)
    packed_k, cache, state = batch_ops.decode_block(
        cfg, params, cache, state, active, 4
    )
    # block k+1 donates BOTH the cache and the state that produced packed_k
    packed_k1, cache, state = batch_ops.decode_block(
        cfg, params, cache, state, active, 4
    )
    got = np.asarray(packed_k)  # must not raise "Array has been deleted"
    assert got.shape == (2, 6)
    assert int(got[0, 5]) >= 1  # n_valid column populated
    assert np.asarray(packed_k1).shape == (2, 6)


@pytest.mark.parametrize("multi_step", [1, 4])
def test_donation_discipline_under_churn(multi_step):
    """Bench-shaped churn (mixed lengths, cancels, slot reuse) on the CPU
    backend, where donated buffers really are deleted: any use-after-donate
    in the dispatch/consume pipeline raises here."""
    import concurrent.futures as cf

    eng = make_engine(
        multi_step=multi_step, max_slots=4,
        admission_per_step=4, max_queue=64,
    )
    eng.start()
    errs: list = []

    def worker(wid: int) -> None:
        for i in range(6):
            fut = eng.submit(
                f"w{wid}r{i} pad pad"[:12],
                max_new_tokens=(1, 3, 9)[i % 3],
                temperature=0.5 if i % 2 else 0.0,
            )
            if i % 4 == 3:
                eng.cancel(fut.request_id)
            try:
                fut.result(timeout=120)
            except Exception as exc:  # noqa: BLE001
                errs.append(exc)

    try:
        with cf.ThreadPoolExecutor(6) as ex:
            list(ex.map(worker, range(6)))
    finally:
        eng.stop()
    assert not errs, errs[:3]


# -- cancellation races -------------------------------------------------------
# Cancel while queued / mid-prefill / mid-stream, each plain and under an
# injected decode fault (the chaos tier's decode.dispatch point): whatever
# the interleaving, the request must reach exactly one terminal state and
# its slot must be reclaimed.

from gofr_tpu import chaos  # noqa: E402

_CANCEL_TERMINAL = ("cancel", "stop", "length")


def _await_terminal(fut, with_fault: bool):
    """Resolve a future under optional fault injection: a normal finish
    reason, or (only when faults are live) the injected fault itself."""
    try:
        res = fut.result(timeout=120)
        assert res.finish_reason in _CANCEL_TERMINAL, res.finish_reason
        return res.finish_reason
    except chaos.ChaosFault:
        assert with_fault, "ChaosFault leaked without an injector installed"
        return "fault"


def _fault_ctx(with_fault: bool):
    import contextlib

    if not with_fault:
        return contextlib.nullcontext()
    return chaos.active(
        chaos.ChaosInjector(41, {"decode.dispatch": 0.5}, max_faults=2)
    )


@pytest.mark.parametrize("with_fault", [False, True])
def test_cancel_while_queued(with_fault):
    eng = make_engine()
    with _fault_ctx(with_fault):
        fut = eng.submit("queued then canceled", max_new_tokens=8)
        eng.cancel(fut.request_id)  # engine not started: still queued
        live = eng.submit("live", max_new_tokens=4)  # keeps decode running
        eng.start()
        try:
            assert _await_terminal(fut, with_fault) in ("cancel", "fault")
            _await_terminal(live, with_fault)
        finally:
            eng.stop()
    assert all(s is None for s in eng.slots)


@pytest.mark.parametrize("with_fault", [False, True])
def test_cancel_mid_prefill(monkeypatch, with_fault):
    eng = make_engine()
    box: dict = {}
    real = batch_ops.prefill_compute

    def cancel_during_prefill(*args, **kw):
        out = real(*args, **kw)
        if "fut" in box:  # cancel lands between prefill compute and commit
            eng.cancel(box["fut"].request_id)
        return out

    monkeypatch.setattr(batch_ops, "prefill_compute", cancel_during_prefill)
    with _fault_ctx(with_fault):
        eng.start()
        try:
            box["fut"] = eng.submit("prefill race", max_new_tokens=16)
            reason = _await_terminal(box["fut"], with_fault)
            # EOS on the very first token legally wins the race → "stop"
            assert reason in ("cancel", "stop", "fault")
        finally:
            eng.stop()
    assert all(s is None for s in eng.slots)


@pytest.mark.parametrize("with_fault", [False, True])
def test_cancel_mid_stream(with_fault):
    eng = make_engine()
    import threading

    got_token = threading.Event()

    def cb(token_id, piece, done):
        if not done:
            got_token.set()

    with _fault_ctx(with_fault):
        eng.start()
        try:
            fut = eng.submit(
                "stream race pad pad", max_new_tokens=48, stream_cb=cb
            )
            # under a decode fault the first token may never arrive — the
            # future fails instead, which is itself a valid terminal state
            arrived = got_token.wait(timeout=60)
            eng.cancel(fut.request_id)
            reason = _await_terminal(fut, with_fault)
            if arrived and reason != "fault":
                assert reason in ("cancel", "stop", "length")
        finally:
            eng.stop()
    assert all(s is None for s in eng.slots)
