"""Digests of the programs the engine lowers for each served family at tiny
widths on the CPU: each family's ``lowered_programs`` (the benchmark's own
lowering — ``prefill_compute`` at each bucket the prompts reach,
``decode_block_paged``, ``ragged_step_paged``) over a ServingEngine built
as the cells build theirs. A change that must leave a family's programs as
they are is held to the digests recorded before it
(``tests/test_lfm2_moe.py``); one that changes them on purpose records
them again:

    python tests/lowered_digests.py

(under ``tests/conftest.py``'s settings, which it repeats).
"""

from __future__ import annotations

import hashlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROMPTS = [6, 40]  # one bucketed prompt, one that chunks


def families() -> dict:
    """name -> (config, lowering, engine settings), the families the
    benchmark serves (the two dense cells share ``llama``: GQA and MHA;
    ``joyai_llm_flash`` is ``deepseek_v32`` without an indexer)."""
    from benchmarks.harness import (
        cohere2_moe_family, deepseek_v32_family, joyai_flash_family, lfm2_moe_family, llama_family,
        nemotron_h_family, phi4flash_family,
    )
    from gofr_tpu.models import cohere2_moe, deepseek_v32, lfm2_moe, llama, nemotron_h, phi4flash

    paged = dict(kv_layout="paged", kv_page_size=4, max_slots=3, max_seq_len=64, prefill_buckets=(16, 32))
    return {
        "llama-gqa": (llama.LlamaConfig.tiny(), llama_family.lowered_programs, dict(paged, prefill_chunk_tokens=16)),
        "llama-mha": (llama.LlamaConfig.tiny(n_kv_heads=4), llama_family.lowered_programs,
                      dict(paged, prefill_chunk_tokens=16)),
        "cohere2_moe": (cohere2_moe.Cohere2MoeConfig.tiny(), cohere2_moe_family.lowered_programs,
                        dict(paged, prefill_chunk_tokens=16)),
        "deepseek_v32": (deepseek_v32.DeepseekV32Config.tiny(), deepseek_v32_family.lowered_programs,
                         dict(paged, prefill_chunk_tokens=16)),
        "phi4flash": (phi4flash.Phi4FlashConfig.tiny(), phi4flash_family.lowered_programs,
                      dict(paged, prefill_chunk_tokens=8)),
        "joyai_llm_flash": (deepseek_v32.DeepseekV32Config.tiny(
            index_n_heads=0, index_head_dim=0, index_topk=0, n_group=1, topk_group=1, rope_factor=1.0,
            rope_theta=3.2e7), joyai_flash_family.lowered_programs, dict(paged, prefill_chunk_tokens=16)),
        "lfm2_moe": (lfm2_moe.Lfm2MoeConfig.tiny(), lfm2_moe_family.lowered_programs,
                     dict(paged, prefill_chunk_tokens=16)),
        "nemotron_h": (nemotron_h.NemotronHConfig.tiny(), nemotron_h_family.lowered_programs,
                       dict(paged, prefill_chunk_tokens=16)),
    }


def digests(name: str) -> dict[str, str]:
    """sha256 of each lowered program of one family."""
    import jax

    from gofr_tpu.serving import ByteTokenizer, EngineConfig, ServingEngine
    from gofr_tpu.serving import batch as batch_ops

    cfg, lowering, settings = families()[name]
    params = batch_ops.model_of(cfg).init_params(cfg, jax.random.PRNGKey(0))
    engine = ServingEngine(cfg, params, EngineConfig(**settings), ByteTokenizer(cfg.vocab_size))
    try:
        texts, _ = lowering(engine, PROMPTS)
    finally:
        engine.stop()
    return {prog: hashlib.sha256(text.encode()).hexdigest() for prog, text in sorted(texts.items())}


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    import jax

    # as tests/conftest.py sets them: the text depends on the precision
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)
    jax.config.update("jax_default_matmul_precision", "highest")
    print(json.dumps({name: digests(name) for name in families()}, indent=1))
