"""Bring-up rules (PR 21): nothing on the serving path may hide the device.

- the kernel predicate (ops/backend.py) names the implementation from the
  platform and raises on one it does not know;
- the serving path refuses a backend nobody asked for;
- the compile cache is placed from outside, at one fixed path otherwise;
- an engine lives on the device it was given, and two engines on two
  devices share nothing;
- ``chip_smoke.py`` runs end to end as a CPU self-test when asked, and
  fails without a TPU when not.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from gofr_tpu.models import llama
from gofr_tpu.ops import backend
from gofr_tpu.serving import ByteTokenizer, EngineConfig, ServingEngine
from gofr_tpu.serving.lora import AdapterRegistry, make_adapter

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_args, env_overrides, timeout=300):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS")}
    env.update(env_overrides)
    args = ([sys.executable, "-c", code_or_args]
            if isinstance(code_or_args, str) else [sys.executable, *code_or_args])
    return subprocess.run(
        args, capture_output=True, text=True, timeout=timeout, cwd=REPO, env=env
    )


# ------------------------------------------------------------ kernel predicate
def test_kernel_mode_by_platform(monkeypatch):
    assert backend.kernel_mode() == backend.REFERENCE  # tests run on the cpu
    assert backend.kernel_mode(True) == backend.INTERPRET
    assert backend.kernel_mode(False) == backend.COMPILED
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert backend.kernel_mode() == backend.COMPILED
    assert backend.kernel_mode(True) == backend.INTERPRET


def test_kernel_mode_raises_on_unknown_platform(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        backend.kernel_mode()
    with pytest.raises(RuntimeError, match="gpu"):
        backend.kernel_mode(True)


# ------------------------------------------------------------- platform guard
_ENGINE_CODE = """
import jax
from gofr_tpu.models import llama
from gofr_tpu.serving import ByteTokenizer, EngineConfig, ServingEngine
cfg = llama.LlamaConfig.tiny(vocab_size=300)
try:
    ServingEngine(cfg, llama.init_params(cfg, jax.random.PRNGKey(0)),
                  EngineConfig(max_slots=2, max_seq_len=32), ByteTokenizer())
    print("ENGINE_BUILT on", jax.default_backend())
except RuntimeError as exc:
    print("REFUSED:", exc)
"""


def test_engine_refuses_a_backend_nobody_asked_for():
    # no JAX_PLATFORMS and no chip: jax drops to the cpu with a warning
    r = _run(_ENGINE_CODE, {})
    assert "REFUSED: no TPU" in r.stdout, r.stdout + r.stderr
    assert "'cpu'" in r.stdout


def test_engine_accepts_the_cpu_when_asked_for():
    r = _run(_ENGINE_CODE, {"JAX_PLATFORMS": "cpu"})
    assert "ENGINE_BUILT on cpu" in r.stdout, r.stdout + r.stderr


# -------------------------------------------------------------- compile cache
_CACHE_CODE = """
import jax
from gofr_tpu.ops.backend import configure_compile_cache
before = jax.config.jax_compilation_cache_dir
print("RESULT", configure_compile_cache(), "|", before, "|",
      jax.config.jax_compilation_cache_dir)
"""


def _cache_probe(env):
    """(returned, configured before, configured after) in a fresh process."""
    r = _run(_CACHE_CODE, {"JAX_PLATFORMS": "cpu", **env})
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT")][0]
    return tuple(p.strip() for p in line[len("RESULT"):].split("|"))


def test_compile_cache_env_set_means_code_sets_nothing(tmp_path):
    where = str(tmp_path / "cache")
    # jax read the variable itself; the call changed nothing
    assert _cache_probe({"JAX_COMPILATION_CACHE_DIR": where}) == (where,) * 3


def test_compile_cache_unset_means_one_fixed_path_in_the_checkout():
    fixed = os.path.join(REPO, ".jax_cache")
    for _ in range(2):  # two processes, one path
        assert _cache_probe({}) == (fixed, "None", fixed)


def test_tpu_client_has_no_cache_or_platform_option():
    from gofr_tpu.config import MapConfig
    from gofr_tpu.datasource.tpu import TPUClient

    client = TPUClient.from_config(MapConfig(
        {"TPU_COMPILE_CACHE_DIR": "/nonexistent", "TPU_PJRT_PLUGIN": "/some.so"},
        use_env=False,
    ))
    assert not hasattr(client, "compile_cache_dir")
    assert not hasattr(client, "platform")
    before = jax.config.jax_compilation_cache_dir
    client.connect()  # a plugin PATH no longer breaks jax.devices(...)
    assert client.device_count() == len(jax.devices())
    assert jax.config.jax_compilation_cache_dir in (
        before, backend.COMPILE_CACHE_DIR
    )
    client.close()


# ------------------------------------------------------------ engine placement
def _leaf_devices(tree):
    return {d for leaf in jax.tree.leaves(tree) for d in leaf.devices()}


def _buffers(tree):
    return {leaf.unsafe_buffer_pointer() for leaf in jax.tree.leaves(tree)}


@pytest.fixture(scope="module")
def tiny_model():
    cfg = llama.LlamaConfig.tiny(vocab_size=300)
    return cfg, llama.init_params(cfg, jax.random.PRNGKey(0))


def test_engine_lives_on_the_device_it_was_given(tiny_model):
    cfg, params = tiny_model
    dev = jax.devices()[3]
    lora = AdapterRegistry(max_active=2, device=dev)
    lora.register(make_adapter(cfg, "a1", rank=2, seed=1))
    engine = ServingEngine(
        cfg, params,
        EngineConfig(max_slots=2, max_seq_len=64, prefill_buckets=(16,),
                     prefill_chunk_tokens=16, kv_page_size=8,
                     prefix_cache_entries=8),
        ByteTokenizer(), lora=lora, device=dev,
    )

    def kv_state():
        pc = engine.paged_cache
        return (pc.k_pool, pc.v_pool)

    assert _leaf_devices(engine.params) == {dev}
    assert _leaf_devices(kv_state()) == {dev}
    assert _leaf_devices((engine.rng, engine._rng_root)) == {dev}
    engine.start()
    try:
        # a bucketed prompt, a chunked one, and an adapter row
        for prompt, kw in (("short", {}), ("x" * 40, {}),
                           ("lora", {"adapter_id": "a1"})):
            out = engine.submit(
                prompt, max_new_tokens=6, temperature=0.0, **kw
            ).result(timeout=120)
            assert out.completion_tokens == 6
        # what the loop thread built and donated through stays there
        assert _leaf_devices(kv_state()) == {dev}
        assert _leaf_devices(engine._dec_state) == {dev}
        assert _leaf_devices(lora.tables()) == {dev}
        cache = engine._prefix_cache
        entries = [cache.get(k) for k in cache.keys()]
        assert entries and _leaf_devices(entries) == {dev}
    finally:
        engine.stop()


def test_two_engines_on_two_devices_share_no_buffers(tiny_model):
    cfg, params = tiny_model
    d1, d2 = jax.devices()[1], jax.devices()[2]
    mk = lambda dev: ServingEngine(  # noqa: E731
        cfg, params, EngineConfig(max_slots=2, max_seq_len=32),
        ByteTokenizer(), device=dev,
    )
    e1, e2 = mk(d1), mk(d2)
    try:
        assert _leaf_devices(e1.params) == {d1}
        assert _leaf_devices(e2.params) == {d2}
        assert not _buffers(e1.params) & _buffers(e2.params)
        pools = lambda e: (e.paged_cache.k_pool, e.paged_cache.v_pool)  # noqa: E731
        assert not _buffers(pools(e1)) & _buffers(pools(e2))
        # neither aliases the caller's tree (it lives on device 0)
        assert not _buffers(params) & (_buffers(e1.params) | _buffers(e2.params))
    finally:
        e1.stop()
        e2.stop()


def test_loadlab_replicas_get_their_own_device(tiny_model):
    from gofr_tpu.loadlab.stack import ServingStack, StackConfig

    cfg, params = tiny_model
    stack = ServingStack(cfg, params, StackConfig(
        roles=("unified", "unified", "unified"), autoscale=False,
        max_seq_len=64, warmup=False,
    ))
    with stack:
        placed = [_leaf_devices(e.params) for e in stack.engines.values()]
        assert all(len(p) == 1 for p in placed)
        assert len(set().union(*placed)) == 3
        out = stack.router.submit(
            "hello", max_new_tokens=4, temperature=0.0
        ).result(timeout=120)
        assert out.completion_tokens == 4


# ----------------------------------------------------------------- chip smoke
def test_chip_smoke_fails_without_a_tpu():
    """The driver's sandbox run: a CPU is not a chip, whatever JAX_PLATFORMS
    says, unless the self-test was asked for. No result line is printed."""
    for env in ({"JAX_PLATFORMS": "cpu"}, {}):
        r = _run(["chip_smoke.py"], env)
        assert r.returncode != 0, r.stdout + r.stderr
        assert '"ok"' not in r.stdout


def test_chip_smoke_cpu_self_test_runs_end_to_end():
    r = _run(["chip_smoke.py", "--cpu-self-test"], {"JAX_PLATFORMS": "cpu"},
             timeout=600)
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-4000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    # labelled as what it is: never a device's name on a CPU result
    assert last == {"ok": True, "cpu_self_test": True,
                    "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    assert "CPU SELF-TEST" in r.stdout
