"""An engine built from ``EngineConfig()`` with nothing set serves every
model family the benchmark serves: its one KV layout is the page pool,
which every family has programs for. Tiny widths, as each family's test
file builds them."""

import jax
import pytest

from gofr_tpu.models import cohere2_moe, deepseek_v32, lfm2_moe, llama, phi4flash
from gofr_tpu.serving import ByteTokenizer, EngineConfig, ServingEngine
from gofr_tpu.serving import batch as batch_ops
from gofr_tpu.serving.kv_cache import PagedKVCache

DEFAULTS = EngineConfig()

FAMILIES = {
    "llama": llama.LlamaConfig.tiny(vocab_size=300),
    "cohere2_moe": cohere2_moe.Cohere2MoeConfig.tiny(vocab_size=300),
    "deepseek_v32": deepseek_v32.DeepseekV32Config.tiny(vocab_size=300),
    "joyai_llm_flash": deepseek_v32.DeepseekV32Config.tiny(
        vocab_size=300, index_n_heads=0, index_head_dim=0, index_topk=0, n_group=1,
        topk_group=1, rope_factor=1.0, rope_theta=3.2e7),
    # its chunk program reads a window's old keys before it writes the
    # chunk over them: the window holds the default chunk, as the
    # published model's 512 does
    "phi4flash": phi4flash.Phi4FlashConfig.tiny(
        vocab_size=300, sliding_window=DEFAULTS.prefill_chunk_tokens),
    "lfm2_moe": lfm2_moe.Lfm2MoeConfig.tiny(vocab_size=300),
}


@pytest.mark.parametrize("name", list(FAMILIES))
def test_the_default_engine_config_serves_every_family(name):
    cfg = FAMILIES[name]
    params = batch_ops.model_of(cfg).init_params(cfg, jax.random.PRNGKey(0))
    engine = ServingEngine(cfg, params, EngineConfig(), ByteTokenizer(cfg.vocab_size))
    try:
        assert engine.config.kv_layout == "paged"
        pc = engine.paged_cache
        assert isinstance(pc, PagedKVCache)
        assert pc.num_pages == DEFAULTS.max_slots * DEFAULTS.max_seq_len // DEFAULTS.kv_page_size
        engine.start()
        res = engine.submit("hello", max_new_tokens=4, temperature=0.0).result(timeout=300)
        assert res.finish_reason == "length" and res.completion_tokens == 4, res
        stats = pc.stats()
        assert stats["free_blocks"] == stats["total_blocks"], stats
    finally:
        engine.stop()
