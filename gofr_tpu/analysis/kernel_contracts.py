"""The committed kernel contract table — ONE source of truth for every
jitted device-kernel entry in ``serving/batch.py``, ``serving/kv_cache.py``
and ``ops/`` (ISSUE 17): positional parameter order, donation set, static
arguments, the packed-output column layout, and symbolic return
signatures.

Everything the data plane trusts implicitly lives here explicitly:

- ``decode_block*`` returns ONE packed ``int32 [B, steps+2]`` array
  (tokens | done | n_valid — :func:`batch._pack_block`); ``ragged_step*``
  appends a ``first`` column ([B, steps+3] — ``_pack_ragged``);
  ``verify_and_sample*`` packs (out | n_accept) into ``[B, T+1]``. The
  host unpack sites (``engine._consume_block``, ``engine._spec_step``)
  slice these columns by offset — a kernel-side pack edit without a
  matching unpack edit silently mis-binds ``done``/``n_valid``/``first``.
- the donated ``DecodeState`` carry is constructed at three independent
  sites (``make_decode_state``, ``admit_decode_state``, the in-kernel
  scatters) that must agree on field set, order and dtypes — PR 15's
  ``adapter`` column had to be threaded through all of them by hand.

This module is PURE DATA (stdlib only, no jax import): the static
analyzer (:mod:`gofr_tpu.analysis.kernelcheck`) loads it on the ``make
lint`` fast path, and the runtime twin (:mod:`gofr_tpu.analysis
.kerneltrace`) ``jax.eval_shape``\\ s every entry against it. ROADMAP
items 2 (flat-packed ragged Pallas kernel) and 3 (tp8 engine) rewrite
exactly these layouts — against this table, not against convention.

Symbolic shape grammar: a return shape is a comma-separated list of
integer expressions over dimension symbols (``"B,steps+2"``); symbols
bind from declared ``arg_shapes`` (single symbols or ``_`` per dim) and
from recorded static int arguments, and an unbound bare symbol binds
greedily to the observed dimension on first use (then must stay
consistent). ``Ret(like=<param>)`` declares a carry passthrough: the
output's full pytree signature must equal that input's — which is what
makes donated-carry drift observable at the eval_shape layer.
"""

from __future__ import annotations

import dataclasses
import json


@dataclasses.dataclass(frozen=True)
class Ret:
    """One positional output of a kernel entry.

    Exactly one of ``shape`` / ``like`` is set: ``shape`` is a symbolic
    dim list (optionally with ``dtype``) for a single array; ``like``
    names an input parameter whose full pytree signature the output must
    reproduce (the donated-carry / cache passthrough contract)."""

    name: str
    shape: str | None = None
    dtype: str | None = None
    like: str | None = None


@dataclasses.dataclass(frozen=True)
class PackedLayout:
    """Column layout of a packed host-sync array: one leading token span
    (symbolic width) then scalar tail columns, all ``dtype``."""

    name: str
    span: str  # symbol naming the token-span width ("steps", "T")
    span_col: str  # what the span columns hold
    scalars: tuple[str, ...]  # tail column names, at span+0, span+1, ...
    dtype: str = "int32"

    @property
    def width(self) -> str:
        return f"{self.span}+{len(self.scalars)}"

    def column_at(self, delta: int) -> str | None:
        """Name of the scalar column at offset ``span + delta``."""
        if 0 <= delta < len(self.scalars):
            return self.scalars[delta]
        return None


PACK_LAYOUTS: dict[str, PackedLayout] = {
    l.name: l
    for l in (
        # decode_block*: _pack_block — [B, steps+2]
        PackedLayout("block", "steps", "tokens", ("done", "n_valid")),
        # ragged_step*: _pack_ragged — [B, steps+3]
        PackedLayout(
            "ragged", "steps", "tokens", ("done", "n_valid", "first")
        ),
        # verify_and_sample*: inline concat — [B, T+1]
        PackedLayout("spec", "T", "out", ("n_accept",)),
    )
}

# Host binding-name vocabularies per scalar column: when an unpack site
# assigns `name = <cast>(packed[row, col])`, the target name must belong
# to the column the offset resolves to — `n_valid = packed[s, steps]`
# (the done column) is exactly the silent mis-bind this rule exists for.
COLUMN_BINDINGS: dict[str, tuple[str, ...]] = {
    "done": ("done", "device_done", "dev_done", "done_flag"),
    "n_valid": ("n_valid", "nvalid", "valid", "n_emitted"),
    "first": ("first", "first_id", "first_tok", "first_token"),
    "n_accept": ("n_accept", "na", "na_np", "accepted", "n_acc"),
}


@dataclasses.dataclass(frozen=True)
class UnpackSite:
    """A host function that slices a packed kernel output after the
    block sync. ``span_names`` are the attribute/variable names that
    denote the token-span width inside that function (``rec.steps``)."""

    file: str
    function: str
    layout: str
    span_names: tuple[str, ...] = ("steps",)


UNPACK_SITES: tuple[UnpackSite, ...] = (
    # _consume_block serves BOTH plain decode blocks and ragged
    # dispatches; it may read the ragged superset's `first` column but
    # must stay consistent with the shared tokens|done|n_valid prefix.
    UnpackSite("gofr_tpu/serving/engine.py", "_consume_block", "ragged"),
    UnpackSite("gofr_tpu/serving/engine.py", "_spec_step", "spec"),
)


@dataclasses.dataclass(frozen=True)
class KernelContract:
    name: str
    file: str
    params: tuple[str, ...]
    donated: tuple[str, ...] = ()
    static: tuple[str, ...] = ()
    packed: str | None = None  # PACK_LAYOUTS key; packed is returns[0]
    pack_helper: str | None = None  # required packing callee in the body
    returns: tuple[Ret, ...] = ()
    # dim-symbol bindings: param -> comma list of symbols / "_" per dim
    arg_shapes: tuple[tuple[str, str], ...] = ()


_BATCH = "gofr_tpu/serving/batch.py"
_KVC = "gofr_tpu/serving/kv_cache.py"
_PAGED_ATTN = "gofr_tpu/ops/paged_attention.py"
_FLASH = "gofr_tpu/ops/flash_attention.py"

# The per-row sampling-parameter tail shared by the ragged entries.
_RAGGED_TAIL = (
    "finish", "new_len", "budgets", "stops", "temps", "topks", "topps",
    "rids", "rng_root", "decode_active", "steps", "adapters", "lora",
)

KERNELS: tuple[KernelContract, ...] = (
    KernelContract(
        "prefill_compute", _BATCH,
        params=("cfg", "params", "tokens", "seq_len"),
        static=("cfg",),
        returns=(
            Ret("last_logits", shape="1,V", dtype="float32"),
            Ret("k_slab", shape="L,S,Hkv,Dh"),
            Ret("v_slab", shape="L,S,Hkv,Dh"),
        ),
        arg_shapes=(("tokens", "_,S"),),
    ),
    KernelContract(
        "insert_slot", _BATCH,
        params=("k_cache", "v_cache", "k_slab", "v_slab", "slot"),
        donated=("k_cache", "v_cache"),
        returns=(Ret("k_cache", like="k_cache"), Ret("v_cache", like="v_cache")),
    ),
    KernelContract(
        "admit_decode_state", _BATCH,
        params=(
            "state", "slots", "tokens", "lens", "budgets", "stops",
            "temps", "topks", "topps", "adapters",
        ),
        donated=("state",),
        returns=(Ret("state", like="state"),),
    ),
    KernelContract(
        "decode_block", _BATCH,
        params=("cfg", "params", "cache", "state", "active", "steps", "lora"),
        donated=("cache", "state"),
        static=("cfg", "steps"),
        packed="block",
        pack_helper="_pack_block",
        returns=(
            Ret("packed", shape="B,steps+2", dtype="int32"),
            Ret("cache", like="cache"),
            Ret("state", like="state"),
        ),
        arg_shapes=(("active", "B"),),
    ),
    KernelContract(
        "decode_block_paged", _BATCH,
        params=(
            "cfg", "params", "k_pool", "v_pool", "state", "block_tables",
            "active", "steps", "lora",
        ),
        donated=("k_pool", "v_pool", "state"),
        static=("cfg", "steps"),
        packed="block",
        pack_helper="_pack_block",
        returns=(
            Ret("packed", shape="B,steps+2", dtype="int32"),
            Ret("k_pool", like="k_pool"),
            Ret("v_pool", like="v_pool"),
            Ret("state", like="state"),
        ),
        arg_shapes=(("active", "B"),),
    ),
    KernelContract(
        "ragged_step", _BATCH,
        params=(
            "cfg", "params", "cache", "state", "chunk", "chunk_start",
        ) + _RAGGED_TAIL,
        donated=("cache", "state"),
        static=("cfg", "steps"),
        packed="ragged",
        pack_helper="_pack_ragged",
        returns=(
            Ret("packed", shape="B,steps+3", dtype="int32"),
            Ret("last_logits", shape="B,V", dtype="float32"),
            Ret("cache", like="cache"),
            Ret("state", like="state"),
        ),
        arg_shapes=(("chunk", "B,C"),),
    ),
    KernelContract(
        "ragged_step_paged", _BATCH,
        params=(
            "cfg", "params", "k_pool", "v_pool", "state", "block_tables",
            "chunk", "chunk_start", "chunk_active", "kv_capacity",
        ) + _RAGGED_TAIL,
        donated=("k_pool", "v_pool", "state"),
        static=("cfg", "steps"),
        packed="ragged",
        pack_helper="_pack_ragged",
        returns=(
            Ret("packed", shape="B,steps+3", dtype="int32"),
            Ret("last_logits", shape="B,V", dtype="float32"),
            Ret("k_pool", like="k_pool"),
            Ret("v_pool", like="v_pool"),
            Ret("state", like="state"),
        ),
        arg_shapes=(("chunk", "B,C"),),
    ),
    KernelContract(
        "insert_chunk", _BATCH,
        params=("k_cache", "v_cache", "k_slab", "v_slab", "slot", "start"),
        donated=("k_cache", "v_cache"),
        returns=(Ret("k_cache", like="k_cache"), Ret("v_cache", like="v_cache")),
    ),
    KernelContract(
        "verify_and_sample", _BATCH,
        params=(
            "cfg", "params", "cache", "chunk", "start_len", "temperature",
            "top_k", "top_p", "rng",
        ),
        donated=("cache",),
        static=("cfg",),
        packed="spec",
        returns=(
            Ret("packed", shape="B,T+1", dtype="int32"),
            Ret("cache", like="cache"),
            Ret("rng", like="rng"),
        ),
        arg_shapes=(("chunk", "B,T"),),
    ),
    KernelContract(
        "verify_and_sample_paged", _BATCH,
        params=(
            "cfg", "params", "k_pool", "v_pool", "block_tables", "chunk",
            "start_len", "active", "kv_capacity", "temperature", "top_k",
            "top_p", "rng",
        ),
        donated=("k_pool", "v_pool"),
        static=("cfg",),
        packed="spec",
        returns=(
            Ret("packed", shape="B,T+1", dtype="int32"),
            Ret("k_pool", like="k_pool"),
            Ret("v_pool", like="v_pool"),
            Ret("rng", like="rng"),
        ),
        arg_shapes=(("chunk", "B,T"),),
    ),
    KernelContract(
        "lora_adjust_logits", _BATCH,
        params=("embedding", "a_row", "b_row", "token", "logits"),
        returns=(Ret("logits", like="logits"),),
    ),
    # an admission's first token on the host path: ops/sampling.sample_logits
    # as one program, the request's three sampling parameters as scalars
    KernelContract(
        "sample_first_token", _BATCH,
        params=("logits", "key", "temperature", "top_k", "top_p"),
        returns=(Ret("ids", shape="1", dtype="int32"),),
    ),
    KernelContract(
        "_write_pages", _KVC,
        params=("k_pool", "v_pool", "k_slab", "v_slab", "page_ids"),
        donated=("k_pool", "v_pool"),
        returns=(Ret("k_pool", like="k_pool"), Ret("v_pool", like="v_pool")),
    ),
    # ``_write_pages`` for a cache of several pools and a per-slot state
    # (a model's ``cache_spec``): the pools and slabs are dicts by pool
    # name, ``k_pool["state"]`` the state arrays, written at ``slot``.
    KernelContract(
        "_write_slot", _KVC,
        params=("k_pool", "v_pool", "k_slab", "v_slab", "page_ids", "slot"),
        donated=("k_pool", "v_pool"),
        returns=(Ret("k_pool", like="k_pool"), Ret("v_pool", like="v_pool")),
    ),
    # The paged decode kernel: a program a row, the pools left in HBM,
    # pages fetched by the kernel's own DMAs in a loop whose trip count is
    # the row's length. Tile sizes are worked out inside from these shapes,
    # so the entry has no parameter for them. POOL RANKS: with ``layer``
    # the entry takes the WHOLE pools [L, N_pages, Hkv, page, Dh] and reads
    # that layer's pages (what the decode step passes: XLA never slices a
    # pool); with ``layer=None`` the pools are ONE layer's
    # [N_pages, Hkv, page, Dh].
    KernelContract(
        "paged_decode_attention", _PAGED_ATTN,
        # window: None builds the kernel without the argument; a scalar
        # (traced: a scanned layer's own) starts a row's loop at the block
        # that holds seq_len - window. layer: None, or the scalar (traced:
        # the layer scan's index) that the page DMAs address
        params=("q", "k_pool", "v_pool", "block_tables", "seq_lens",
                "scale", "interpret", "window", "layer"),
        static=("scale", "interpret"),
        returns=(Ret("out", like="q"),),
    ),
    # The decode step's append: one token's K/V a row into
    # [layer, pages[b], :, offsets[b]] of the whole pools, by a Pallas call
    # aliased over both (a read-modify-write of the page a row writes; no
    # two live rows share a page). Not donated here — the serving programs
    # that inline it donate the pools, and the aliasing makes it in place.
    KernelContract(
        "paged_kv_append", _PAGED_ATTN,
        params=("k_pool", "v_pool", "k_new", "v_new", "layer", "pages",
                "offsets", "interpret"),
        static=("interpret",),
        returns=(Ret("k_pool", like="k_pool"), Ret("v_pool", like="v_pool")),
    ),
    KernelContract(
        "flash_attention", _FLASH,
        params=("q", "k", "v", "kv_len", "causal", "scale", "block_q",
                "block_k", "interpret", "window"),
        # scale: None (1/sqrt(D)) or a Python float — a traced scalar would
        # be a constant the kernel captures, which Mosaic refuses
        static=("causal", "scale", "block_q", "block_k", "interpret"),
        returns=(Ret("out", like="q"),),
    ),
)

CONTRACTS: dict[str, KernelContract] = {k.name: k for k in KERNELS}

# Files whose module-level jitted defs MUST each carry a contract above
# (the coverage audit: a new kernel entry without a declared contract
# fails the build).
KERNEL_FILES: tuple[str, ...] = (_BATCH, _KVC, _PAGED_ATTN, _FLASH)


def contracts_for_file(rel_path: str) -> dict[str, KernelContract]:
    return {k.name: k for k in KERNELS if k.file == rel_path}


# ---------------------------------------------------------------- carry
# The donated DecodeState carry: field set, ORDER, and dtypes. Every
# construction site (the dataclass itself, tree_flatten, make_decode_state,
# admit_decode_state, the in-kernel scatter/fold constructors) must agree.
CARRY_CLASS = "DecodeState"
CARRY_FILE = _BATCH
DECODE_STATE_FIELDS: tuple[tuple[str, str], ...] = (
    ("last_token", "int32"),
    ("seq_len", "int32"),
    ("done", "bool"),
    ("budget", "int32"),
    ("stop_tok", "int32"),
    ("temperature", "float32"),
    ("top_k", "int32"),
    ("top_p", "float32"),
    ("rng", "key"),
    ("adapter", "int32"),
)
CARRY_CONSTRUCTORS: tuple[str, ...] = (
    "make_decode_state", "admit_decode_state",
)

# engine._pending_admit host-side tuple: (first_token, resident_len,
# budget, stop_id, adapter_slot) — arity must match everywhere it is
# built, annotated, and unpacked into admit_decode_state.
ADMIT_TUPLE_FIELDS: tuple[str, ...] = (
    "first_token", "resident_len", "budget", "stop_id", "adapter_slot",
)
ADMIT_TUPLE_ATTR = "_pending_admit"
ADMIT_TUPLE_FILE = "gofr_tpu/serving/engine.py"


# ------------------------------------------------------------- symbolics
def eval_dims(shape: str, env: dict[str, int]) -> tuple[int, ...] | None:
    """Evaluate a symbolic dim list against ``env``; None when a symbol
    is unbound (callers may bind-on-first-use for bare symbols)."""
    dims: list[int] = []
    for part in shape.split(","):
        try:
            dims.append(
                int(eval(part, {"__builtins__": {}}, dict(env)))  # noqa: S307
            )
        except NameError:
            return None
    return tuple(dims)


def render_table_json() -> str:
    """The static contract table as JSON (``--kernel-table``)."""
    return json.dumps(
        {
            "kernels": [dataclasses.asdict(k) for k in KERNELS],
            "layouts": {
                n: dataclasses.asdict(l) for n, l in PACK_LAYOUTS.items()
            },
            "carry": {
                "class": CARRY_CLASS,
                "file": CARRY_FILE,
                "fields": [list(f) for f in DECODE_STATE_FIELDS],
            },
            "admit_tuple": {
                "attr": ADMIT_TUPLE_ATTR,
                "file": ADMIT_TUPLE_FILE,
                "fields": list(ADMIT_TUPLE_FIELDS),
            },
            "unpack_sites": [dataclasses.asdict(u) for u in UNPACK_SITES],
            "kernel_files": list(KERNEL_FILES),
        },
        indent=2,
        sort_keys=True,
    )
