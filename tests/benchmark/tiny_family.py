"""A second family for the CPU rehearsal: a factory, a lowering and a plain
reference that live here and are named nowhere in ``benchmarks/harness/`` —
the harness reaches them through the keys of the configuration's file
(``cellbench_tiny.TINY2_CONFIG``: ``factory``, in whose module it finds
``lowered_programs``, and ``reference``), as it will reach another
architecture's.

The program there is serves a dense decoder through a ``LlamaConfig``, so
the factory and the lowering hand on to the family that builds one, and
``CALLS`` records that the harness came this way: the rehearsal proves the
route, and nothing of how a second architecture lowers its programs. The reference is its own:
the same published forward pass as ``benchmarks/harness/reference.py``, in
NumPy and float64, one sequence at a time — a second witness that shares no
code with the first and imports nothing of the program. ``control_bits``
re-quantises every int8 matrix to that many bits per output channel.
"""

from __future__ import annotations

from typing import Any

import numpy as np

CALLS: list[str] = []  # which of the three the harness asked, in order


def build(config: dict[str, Any], seed: int) -> tuple[Any, dict]:
    from benchmarks.harness import llama_family

    CALLS.append("factory")
    return llama_family.build(config, seed)


def lowered_programs(engine: Any, prompt_sizes: list[int]) -> tuple[dict[str, str], tuple[str, ...]]:
    from benchmarks.harness import llama_family

    CALLS.append("lowering")
    return llama_family.lowered_programs(engine, prompt_sizes)


# ------------------------------------------------------------ the reference
def _matrix(w: dict, bits: int) -> np.ndarray:
    full = np.asarray(w["q"]).astype(np.float64) * np.asarray(w["s"]).astype(np.float64)[None, :]
    if bits == 8:
        return full
    levels = float(2 ** (bits - 1) - 1)
    scale = np.maximum(np.abs(full).max(axis=0, keepdims=True) / levels, 1e-30)
    return np.clip(np.round(full / scale), -levels, levels) * scale


def _rms(x: np.ndarray, w: Any, eps: float) -> np.ndarray:
    return x / np.sqrt((x * x).mean(axis=-1, keepdims=True) + eps) * np.asarray(w).astype(np.float64)


def _rope(x: np.ndarray, theta: float) -> np.ndarray:
    T, _, Dh = x.shape
    half = Dh // 2
    ang = np.arange(T)[:, None] / theta ** (np.arange(half) / half)[None, :]
    sin, cos = np.sin(ang)[:, None, :], np.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def logits(config: dict[str, Any], weights: dict, token_ids: Any, weight_bits: int = 8) -> np.ndarray:
    """Logits [T, V] at every position of one sequence [T]."""
    CALLS.append("logits")
    H, Hkv, Dh = (int(config[k]) for k in ("num_attention_heads", "num_key_value_heads", "head_dim"))
    theta, eps = float(config["rope_theta"]), float(config["rms_norm_eps"])
    ids = np.asarray(token_ids)
    T = len(ids)
    x = np.asarray(weights["embedding"])[ids].astype(np.float64)
    causal = np.tril(np.ones((T, T), bool))
    for i in range(int(config["num_hidden_layers"])):
        lp = {k: ({"q": v["q"][i], "s": v["s"][i]} if isinstance(v, dict) else v[i])
              for k, v in weights["layers"].items()}
        h = _rms(x, lp["attn_norm"], eps)
        q = _rope((h @ _matrix(lp["wq"], weight_bits)).reshape(T, H, Dh), theta)
        k = _rope((h @ _matrix(lp["wk"], weight_bits)).reshape(T, Hkv, Dh), theta)
        v = (h @ _matrix(lp["wv"], weight_bits)).reshape(T, Hkv, Dh)
        k, v = np.repeat(k, H // Hkv, axis=1), np.repeat(v, H // Hkv, axis=1)
        scores = np.where(causal[None], np.einsum("thd,shd->hts", q, k) / np.sqrt(Dh), -np.inf)
        probs = np.exp(scores - scores.max(axis=-1, keepdims=True))
        probs /= probs.sum(axis=-1, keepdims=True)
        x = x + np.einsum("hts,shd->thd", probs, v).reshape(T, H * Dh) @ _matrix(lp["wo"], weight_bits)
        h = _rms(x, lp["mlp_norm"], eps)
        gate = h @ _matrix(lp["w_gate"], weight_bits)
        up = h @ _matrix(lp["w_up"], weight_bits)
        x = x + (gate / (1.0 + np.exp(-gate)) * up) @ _matrix(lp["w_down"], weight_bits)
    return _rms(x, weights["final_norm"], eps) @ _matrix(weights["lm_head"], weight_bits)


def pad_to(n: int, multiple: int) -> int:
    return n  # nothing compiles here: a sequence runs at its own length


def served_gaps(config: dict[str, Any], weights: dict, prompt: list[int], served: list[int],
                pad_len: int = 0, control_bits: int | None = None) -> dict[str, np.ndarray]:
    """``served``: how far each served token's logit lies below the best;
    with ``control_bits`` also ``control``: at the same positions, the gap
    of the token the lower precision puts first."""
    CALLS.append("served_gaps")
    ids = np.asarray(list(prompt) + list(served), np.int64)
    rows = slice(len(prompt) - 1, len(prompt) - 1 + len(served))
    ref = logits(config, weights, ids)[rows]
    best = ref.max(axis=-1)
    out = {"served": best - ref[np.arange(len(served)), np.asarray(served)]}
    if control_bits is not None:
        first = logits(config, weights, ids, weight_bits=control_bits)[rows].argmax(axis=-1)
        out["control"] = best - ref[np.arange(len(served)), first]
    return out
