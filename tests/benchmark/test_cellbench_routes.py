"""The routes by which the runner reaches one architecture: the factory,
the lowering and the reference that a configuration's file names; the byte
tokenizer restated in one neutral place; and the engine's device state
freed from the object, whatever its fields are called."""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cellbench_tiny
from benchmarks.harness import llama_family, manifest, reference, runner, tokens

FAMILY = "tests.benchmark.tiny_family"


@pytest.mark.parametrize("config, module, lowers", [
    (cellbench_tiny.TINY_CONFIG, "benchmarks.harness.reference", "benchmarks.harness.llama_family:lowered_programs"),
    (cellbench_tiny.TINY2_CONFIG, FAMILY, FAMILY + ":lowered_programs"),
], ids=["the_harness_s_family", "a_family_of_its_own"])
def test_a_file_names_its_reference_and_its_lowering_is_beside_its_factory(config, module, lowers):
    found = manifest.reference_module(config)
    assert found is importlib.import_module(module) and found is manifest.reference_module(config)
    assert manifest.lowering(config) is manifest.resolve(lowers)
    # one way to name a lowering: a ``lowering`` key in the file is not read
    assert manifest.lowering(dict(config, lowering="os.path:join")) is manifest.resolve(lowers)


@pytest.mark.parametrize("path, reason", [
    ("", "from the root of the checkout"), ("/abs/reference.py", "from the root of the checkout"),
    ("tests/../x.py", "from the root of the checkout"), ("benchmarks.harness.reference", "from the root of the checkout"),
    ("tests/benchmark/fixtures/no_gaps_reference.py", "lacks served_gaps"),
])
def test_a_reference_is_a_path_from_the_root_of_the_checkout_to_a_module_with_the_contract(path, reason):
    with pytest.raises((ValueError, AttributeError), match=reason):
        manifest.reference_module({"reference": path})


@pytest.mark.parametrize("text", ["", "plain ascii", "ünïcödé € 漢字", "\x00\x7f tabs\tand\nlines"])
def test_the_restated_tokenizer_is_the_programs(text):
    from gofr_tpu.serving import ByteTokenizer

    program = ByteTokenizer(320)
    assert tokens.prompt_ids(text) == program.encode(text)
    assert (tokens.BOS_ID, tokens.EOS_ID, tokens.BYTE_OFFSET) == (program.bos_id, program.eos_id, program._offset)


@pytest.mark.parametrize("seed", [77, 2**31 + 78])
def test_the_two_references_share_no_code_and_agree(seed):
    """NumPy float64 against jax float32 at ``highest``, on the same
    weights: two witnesses of one published forward pass. (Not so their
    controls: a tie in the int4 rounding falls either way by the type.)"""
    family = importlib.import_module(FAMILY)
    cfg = cellbench_tiny.TINY2_CONFIG
    weights = llama_family.make_weights(cfg, seed)
    ids = np.asarray([1] + list(np.random.default_rng(5).integers(3, 259, 47)), np.int32)
    a = np.asarray(reference.logits(cfg, weights, ids))
    b = family.logits(cfg, weights, ids)
    assert a.shape == b.shape == (48, cfg["vocab_size"]) and np.abs(a).max() > 1.0
    assert np.abs(a - b).max() < 1e-3


def test_the_family_lowers_the_programs_the_warm_up_uses_and_names_the_one_that_holds_a_kernel():
    class Pager:
        k_pool = v_pool = jnp.zeros((2, 9, 2, 16, 16), jnp.bfloat16)
        max_pages_per_seq = 8

    cfg, params = llama_family.build(cellbench_tiny.TINY_CONFIG, 3)
    engine = type("Engine", (), dict(
        model_cfg=cfg, params=params, config=runner.engine_config({"engine": cellbench_tiny.ENGINE}),
        paged_cache=Pager, _chunk_tokens=64, _block_steps=4, _rng_root=jax.random.PRNGKey(0),
        _buckets=lambda self: (32, 64), _route_chunked=lambda self, n: n > 64))()
    texts, must_hold = llama_family.lowered_programs(engine, [20, 60, 100])
    assert list(texts) == ["prefill_compute[32]", "prefill_compute[64]", "decode_block_paged", "ragged_step_paged"]
    assert must_hold == ("decode_block_paged",) and all("func.func" in t or "HloModule" in t for t in texts.values())
    paths, bare = runner.mosaic_calls(cellbench_tiny.TINY_CONFIG, engine, [20])
    # on the CPU no program holds a Mosaic call: the runner counts none and says which one should have
    assert paths == {"prefill_compute[32]": 0, "decode_block_paged": 0} and bare == ["decode_block_paged"]
    # a cache this family cannot lower is the family's to refuse, not the runner's
    engine.paged_cache = None
    with pytest.raises(ValueError, match="set kv_layout to paged"):
        llama_family.lowered_programs(engine, [20])


# ------------------------------------------------------ freeing the state
class Holder:
    """An engine-shaped object: device arrays behind an attribute, a
    container, a closure and a partial; the weights too, a second time."""

    def __init__(self, weights):
        hidden, bound = jnp.ones((3, 3)), jnp.ones(5)
        self.params = weights
        self.view = {"again": weights["w"]}
        self.pools = {"sliding": (jnp.ones((4, 8)), jnp.ones((4, 8))), "full": [jnp.ones((2, 8))]}
        self.closure = lambda: hidden
        self.partial = functools.partial(jnp.add, bound)
        self.host = np.ones(5)


def test_every_device_array_beside_the_weights_is_freed_whatever_holds_it():
    older = jax.live_arrays()       # other tests' arrays, and this one's bystander
    bystander = jnp.arange(4.0)
    older.append(bystander)
    weights = {"w": jnp.ones((8, 8)), "layers": {"s": jnp.ones(8)}}
    holder = Holder(weights)
    arrays = [*holder.pools["sliding"], holder.pools["full"][0], holder.closure(), holder.partial.args[0]]
    freed = runner.free_device_state(weights, older)
    assert freed >= sum(a.nbytes for a in arrays) and all(a.is_deleted() for a in arrays)
    assert not any(x.is_deleted() for x in jax.tree.leaves(weights)) and not bystander.is_deleted()
    assert float(holder.view["again"].sum()) == 64.0 and holder.host.sum() == 5
    assert runner.free_device_state(weights, older) == 0  # nothing is left to find
