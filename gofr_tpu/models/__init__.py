"""Model families.

The serving framework's model zoo (BASELINE.json configs):
- llama: decoder-only LLM family (Llama-3 shapes; flagship)
- cohere2_moe: parallel-block decoder with sparse and shared experts, window
  and full attention by layer type (served on the paged path, also as one
  chip's share of an expert-parallel deployment)
- deepseek_v32: latent attention under a learned sparse selection, leading
  dense layers, group-limited sparse experts beside a shared one (served on
  the paged path, also as one chip's share); without the selection, the
  DeepSeek-V3 layer JoyAI-LLM-Flash publishes (one latent pool)
- phi4flash: state-space and window-attention layers under one full-attention
  layer whose K and V the whole upper half reads, gated memory units,
  differential attention (served on the paged path: a recurrent state a slot
  beside two pools)
- lfm2_moe: gated short convolutions and per-head QK-normed attention in an
  irregular order, two dense layers then sigmoid experts with an expert bias
  (served on the paged path: conv tails a slot beside one pool)
- nemotron_h: Mamba-2 mixers, relu² experts beside a shared one and GQA with
  no positional encoding in the published pattern (served on the paged path:
  SSD states and conv tails a slot beside one pool, also as one chip's share)
- bert: encoder embedder (/embed endpoint)
- whisper: encoder-decoder ASR (async Pub/Sub path)

All models are pure-functional JAX: a config dataclass, an ``init`` returning
a params pytree, and jit-compiled apply functions. Layers are stacked and
scanned (lax.scan) so compile time is flat in depth; weights are bf16 by
default with f32 accumulation inside ops.
"""

from gofr_tpu.models import bert, cohere2_moe, deepseek_v32, lfm2_moe, llama, nemotron_h, phi4flash

__all__ = ["llama", "cohere2_moe", "deepseek_v32", "phi4flash", "lfm2_moe", "nemotron_h", "bert"]
