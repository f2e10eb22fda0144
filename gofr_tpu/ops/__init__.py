"""TPU compute ops: norms, rotary embeddings, attention, sampling.

All ops are pure jax (traced once under jit, static shapes, fused by XLA);
the hot attention paths have Pallas TPU kernels in ops/flash_attention.py
(prefill: a grid over query and key blocks) and ops/paged_attention.py
(decode from the paged pool: one program a row, which loops over the pages
the row owns and fetches them itself; ops/latent_attention.py is its form
for one latent row shared by every head), and ops/expert_rows.py computes the
every-row expert sum of ops/moe.py as one call a layer. ops/backend.py
decides, at trace time and in one place, what each runs: the
Mosaic-compiled kernel on a TPU, the XLA reference on the CPU (the Pallas
interpreter when a test asks), an error anywhere else.
"""

from gofr_tpu.ops.norms import layer_norm, rms_norm
from gofr_tpu.ops.rope import apply_rope, rope_table
from gofr_tpu.ops.attention import attention, decode_attention, gqa_repeat
from gofr_tpu.ops.sampling import sample_logits

__all__ = [
    "rms_norm",
    "layer_norm",
    "rope_table",
    "apply_rope",
    "attention",
    "decode_attention",
    "gqa_repeat",
    "sample_logits",
]
