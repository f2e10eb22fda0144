"""shardcheck (gofr_tpu/analysis/shardcheck.py): SPMD/collective
consistency, use-after-donation and retrace-hazard rule fixtures, the
JSON output format, and the ratchet-baseline round trip.
docs/static-analysis.md documents the rule catalog these pin down."""

from __future__ import annotations

import json
import os

from gofr_tpu.analysis import baseline_io
from gofr_tpu.analysis.core import Finding, run_rules
from gofr_tpu.analysis.rules import default_rules

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MESH_DECL = 'AXIS_ORDER = ("dp", "tp", "sp")\n'


def lint_tree(tmp_path, files: dict[str, str]):
    """Materialize {relpath: source} under tmp_path and lint the top dir."""
    for rel, source in files.items():
        full = tmp_path / rel
        full.parent.mkdir(parents=True, exist_ok=True)
        full.write_text(source)
    top = tmp_path / sorted(files)[0].split("/")[0]
    return run_rules([str(top)], default_rules())


def rules_of(findings):
    return sorted(f.rule for f in findings)


# ------------------------------------------------------------- mesh axes
def test_mesh_axis_typo_in_partition_spec(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/parallel/mesh.py": MESH_DECL,
        "gofr_tpu/parallel/rules.py": (
            "from jax.sharding import PartitionSpec as P\n"
            'SPEC = P("tpu", None)\n'  # typo: tpu for tp
        ),
    })
    assert rules_of(findings) == ["mesh-axis-unknown"]
    assert "'tpu'" in findings[0].message and findings[0].line == 2


def test_mesh_axis_unknown_collective_axis_name(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/parallel/mesh.py": MESH_DECL,
        "gofr_tpu/parallel/cp.py": (
            "import jax\n"
            "from jax import shard_map\n"
            "def body(x):\n"
            '    return jax.lax.psum(x, "fsdp")\n'  # not in this mesh
            "def wrap(x, mesh):\n"
            "    return shard_map(body, mesh=mesh)(x)\n"
        ),
    })
    assert rules_of(findings) == ["mesh-axis-unknown"]
    assert "'fsdp'" in findings[0].message


def test_mesh_axis_nested_tuple_and_defaults_clean(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/parallel/mesh.py": MESH_DECL,
        "gofr_tpu/parallel/rules.py": (
            "from jax.sharding import PartitionSpec as P\n"
            'SPEC = P(("dp", "tp"), "sp", None)\n'
            'def ring(x, axis="sp"):\n'
            "    return x\n"
        ),
    })
    assert findings == []


def test_mesh_axis_names_keyword_declaration_form(tmp_path):
    # Mesh(devices, axis_names=(...)) declares the vocabulary too
    findings = lint_tree(tmp_path, {
        "gofr_tpu/parallel/mesh.py": (
            "from jax.sharding import Mesh\n"
            "def build(devices):\n"
            '    return Mesh(devices, axis_names=("dp", "tp"))\n'
        ),
        "gofr_tpu/parallel/rules.py": (
            "from jax.sharding import PartitionSpec as P\n"
            'GOOD = P("dp", "tp")\n'
            'BAD = P("model", None)\n'
        ),
    })
    assert rules_of(findings) == ["mesh-axis-unknown"]
    assert "'model'" in findings[0].message


def test_mesh_axis_skipped_without_mesh_declaration(tmp_path):
    # partial lint (a subtree with no mesh construction) must not flood
    findings = lint_tree(tmp_path, {
        "gofr_tpu/parallel/rules.py": (
            "from jax.sharding import PartitionSpec as P\n"
            'SPEC = P("anything", None)\n'
        ),
    })
    assert findings == []


def test_mesh_axis_suppression_honored(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/parallel/mesh.py": MESH_DECL,
        "gofr_tpu/parallel/rules.py": (
            "from jax.sharding import PartitionSpec as P\n"
            'SPEC = P("expert", None)'
            "  # gofrlint: disable=mesh-axis-unknown -- bound by a caller mesh\n"
        ),
    })
    assert findings == []


# ------------------------------------------------------- collective mapping
def test_collective_with_literal_axis_outside_shard_map(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/parallel/mesh.py": MESH_DECL,
        "gofr_tpu/parallel/bad.py": (
            "import jax\n"
            "def grad_sync(g):\n"
            '    return jax.lax.psum(g, "dp")\n'
        ),
    })
    assert rules_of(findings) == ["collective-unmapped"]
    assert "psum" in findings[0].message


def test_collective_at_module_scope_flagged(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/parallel/mesh.py": MESH_DECL,
        "gofr_tpu/parallel/bad.py": (
            "import jax\n"
            'IDX = jax.lax.axis_index("tp")\n'
        ),
    })
    assert rules_of(findings) == ["collective-unmapped"]
    assert "module scope" in findings[0].message


def test_collective_inside_shard_map_body_clean(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/parallel/mesh.py": MESH_DECL,
        "gofr_tpu/parallel/good.py": (
            "import jax\n"
            "from jax import shard_map\n"
            "def wrap(x, mesh):\n"
            "    def body(v):\n"
            '        return jax.lax.psum(v, "tp")\n'
            "    return shard_map(body, mesh=mesh)(x)\n"
        ),
    })
    assert findings == []


def test_collective_in_lambda_passed_to_shard_map_clean(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/parallel/mesh.py": MESH_DECL,
        "gofr_tpu/parallel/good.py": (
            "import jax\n"
            "from jax import shard_map\n"
            "def wrap(x, mesh):\n"
            '    return shard_map(lambda v: jax.lax.psum(v, "tp"), '
            "mesh=mesh)(x)\n"
        ),
    })
    assert findings == []


def test_collective_axis_parameter_convention_clean(tmp_path):
    # the *_sharded(..., axis_name=...) body convention: the caller binds
    # the axis; the wrapper is where the mapping is checked
    findings = lint_tree(tmp_path, {
        "gofr_tpu/parallel/mesh.py": MESH_DECL,
        "gofr_tpu/parallel/good.py": (
            "import jax, functools\n"
            "from jax import shard_map\n"
            "def ring_sharded(x, *, axis_name):\n"
            "    return jax.lax.pmean(x, axis_name)\n"
            "def ring(x, mesh, axis):\n"
            "    fn = functools.partial(ring_sharded, axis_name=axis)\n"
            "    return shard_map(fn, mesh=mesh)(x)\n"
        ),
    })
    assert findings == []


# ------------------------------------------------------- use after donation
DONATING = (
    "from functools import partial\n"
    "import jax\n"
    "@partial(jax.jit, donate_argnums=(0,))\n"
    "def step(cache, tok):\n"
    "    return cache + tok, tok\n"
)


def test_use_after_donation_positive(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/batch.py": DONATING,
        "gofr_tpu/serving/engine.py": (
            "from gofr_tpu.serving.batch import step\n"
            "def drive(cache, tok):\n"
            "    new_cache, t = step(cache, tok)\n"
            "    return cache + 1\n"  # donated buffer, re-read
        ),
    })
    assert rules_of(findings) == ["use-after-donation"]
    assert "step()" in findings[0].message and findings[0].line == 4


def test_use_after_donation_attribute_chain(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/batch.py": DONATING,
        "gofr_tpu/serving/engine.py": (
            "from gofr_tpu.serving.batch import step\n"
            "class Engine:\n"
            "    def drive(self, tok):\n"
            "        out, t = step(self.cache.k, tok)\n"
            "        return self.cache.k.sum()\n"
        ),
    })
    assert rules_of(findings) == ["use-after-donation"]
    assert "'self.cache.k'" in findings[0].message


def test_donation_rebind_idiom_clean(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/batch.py": DONATING,
        "gofr_tpu/serving/engine.py": (
            "from gofr_tpu.serving.batch import step\n"
            "def drive(cache, tok):\n"
            "    cache, t = step(cache, tok)\n"  # x = f(x): the idiom
            "    return cache + 1\n"
        ),
    })
    assert findings == []


def test_donation_metadata_reads_and_rebind_kill_clean(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/batch.py": DONATING,
        "gofr_tpu/serving/engine.py": (
            "from gofr_tpu.serving.batch import step\n"
            "def drive(cache, tok):\n"
            "    out, t = step(cache, tok)\n"
            "    shape = cache.shape\n"  # aval metadata survives donation
            "    cache = out\n"          # rebound before any buffer read
            "    return cache, shape\n"
        ),
    })
    assert findings == []


def test_donation_read_in_later_method_not_flagged(tmp_path):
    # methods run at independent times: a read in another method is not
    # sequenced after the donating call
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/batch.py": DONATING,
        "gofr_tpu/serving/engine.py": (
            "from gofr_tpu.serving.batch import step\n"
            "class Engine:\n"
            "    def drive(self, tok):\n"
            "        out, t = step(self.cache, tok)\n"
            "        self.cache = out\n"
            "    def probe(self):\n"
            "        return self.cache\n"
        ),
    })
    assert findings == []


def test_donation_conditional_rebind_clean(tmp_path):
    # `if full: k = flush(k)` rebinds inside the branch — the later read
    # is of the rebound name, not the donated buffer
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/batch.py": DONATING,
        "gofr_tpu/serving/engine.py": (
            "from gofr_tpu.serving.batch import step\n"
            "def drive(cache, tok, full):\n"
            "    if full:\n"
            "        cache, tok = step(cache, tok)\n"
            "    return cache.sum()\n"
        ),
    })
    assert findings == []


def test_donation_in_loop_without_rebind_flagged(tmp_path):
    # the next iteration re-reads the deleted buffer via the call's args
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/batch.py": DONATING,
        "gofr_tpu/serving/engine.py": (
            "from gofr_tpu.serving.batch import step\n"
            "def drive(cache, toks):\n"
            "    outs = []\n"
            "    for tok in toks:\n"
            "        out, t = step(cache, tok)\n"
            "        outs.append(out)\n"
            "    return outs\n"
        ),
    })
    assert rules_of(findings) == ["use-after-donation"]
    assert "inside a loop" in findings[0].message


def test_donation_self_referencing_rebind_flagged(tmp_path):
    # `cache = cache + 1` READS the deleted buffer before storing — the
    # value executes before the target despite AST field order
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/batch.py": DONATING,
        "gofr_tpu/serving/engine.py": (
            "from gofr_tpu.serving.batch import step\n"
            "def drive(cache, tok):\n"
            "    out, t = step(cache, tok)\n"
            "    cache = cache + 1\n"
            "    return cache\n"
        ),
    })
    assert rules_of(findings) == ["use-after-donation"]
    findings = lint_tree(tmp_path / "aug", {
        "gofr_tpu/serving/batch.py": DONATING,
        "gofr_tpu/serving/engine.py": (
            "from gofr_tpu.serving.batch import step\n"
            "def drive(cache, tok):\n"
            "    out, t = step(cache, tok)\n"
            "    cache += 1\n"
            "    return cache\n"
        ),
    })
    assert rules_of(findings) == ["use-after-donation"]


def test_donation_local_same_name_function_shadows_registry(tmp_path):
    # b.py's own plain `step` is not the donating jit from batch.py
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/batch.py": DONATING,
        "gofr_tpu/models/other.py": (
            "def step(cache, tok):\n"
            "    return cache + tok, tok\n"
            "def drive(cache, tok):\n"
            "    out, t = step(cache, tok)\n"
            "    return cache + 1\n"
        ),
    })
    assert findings == []


def test_donation_in_compound_header_flagged(tmp_path):
    # a donating call in an `if` test still deletes the buffer
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/batch.py": DONATING,
        "gofr_tpu/serving/engine.py": (
            "from gofr_tpu.serving.batch import step\n"
            "def drive(cache, tok):\n"
            "    if step(cache, tok) is None:\n"
            "        return None\n"
            "    return cache + 1\n"
        ),
    })
    assert rules_of(findings) == ["use-after-donation"]


def test_donation_of_loop_iteration_variable_clean(tmp_path):
    # `for cache in caches:` rebinds cache from the iterator each pass —
    # every iteration donates a fresh buffer
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/batch.py": DONATING,
        "gofr_tpu/serving/engine.py": (
            "from gofr_tpu.serving.batch import step\n"
            "def drive(caches, tok):\n"
            "    outs = []\n"
            "    for cache in caches:\n"
            "        out, t = step(cache, tok)\n"
            "        outs.append(out)\n"
            "    return outs\n"
        ),
    })
    assert findings == []


def test_donation_in_loop_with_rebind_clean(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/batch.py": DONATING,
        "gofr_tpu/serving/engine.py": (
            "from gofr_tpu.serving.batch import step\n"
            "def drive(cache, toks):\n"
            "    for tok in toks:\n"
            "        cache, t = step(cache, tok)\n"
            "    return cache\n"
        ),
    })
    assert findings == []


def test_donation_alias_captured_before_call_flagged(tmp_path):
    """The dispatch shape that escaped the rule and crashed the round-4
    TPU engine bench (int32[32]): a reference captured into another name
    BEFORE the donating call — here a constructor capture, exactly the
    engine's old ``_Inflight(last_tok, ...)`` — is read after the call
    even though the donated name itself was rebound in the same
    statement."""
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/batch.py": DONATING,
        "gofr_tpu/serving/engine.py": (
            "from gofr_tpu.serving.batch import step\n"
            "import numpy as np\n"
            "class Inflight:\n"
            "    def __init__(self, tok):\n"
            "        self.next_token = tok\n"
            "def drive(cache, tok):\n"
            "    rec = Inflight(cache)\n"
            "    cache, t = step(cache, tok)\n"  # rebind: the old rule passed
            "    return np.sum(rec.next_token)\n"  # reads the deleted buffer
        ),
    })
    assert rules_of(findings) == ["use-after-donation"]
    assert "'rec'" in findings[0].message and "captured" in findings[0].message


def test_donation_direct_alias_copy_flagged(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/batch.py": DONATING,
        "gofr_tpu/serving/engine.py": (
            "from gofr_tpu.serving.batch import step\n"
            "def drive(cache, tok):\n"
            "    keep = cache\n"
            "    cache, t = step(cache, tok)\n"
            "    return keep + 1\n"
        ),
    })
    assert rules_of(findings) == ["use-after-donation"]
    assert "'keep'" in findings[0].message


def test_donation_alias_rebound_before_read_clean(tmp_path):
    """Rebinding the alias from the call's OUTPUT before any read sheds
    the captured reference — the correct fix shape."""
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/batch.py": DONATING,
        "gofr_tpu/serving/engine.py": (
            "from gofr_tpu.serving.batch import step\n"
            "def drive(cache, tok):\n"
            "    keep = cache\n"
            "    cache, t = step(cache, tok)\n"
            "    keep = cache\n"
            "    return keep + 1\n"
        ),
    })
    assert findings == []


def test_donation_alias_attribute_store_is_not_a_read(tmp_path):
    """Setting an unrelated field ON the alias after the donating call
    never reads the captured buffer — the inner Name's Load ctx inside an
    Attribute store target must not masquerade as a use-after-donation
    (code-review: this was a false lint failure)."""
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/batch.py": DONATING,
        "gofr_tpu/serving/engine.py": (
            "from gofr_tpu.serving.batch import step\n"
            "class Holder:\n"
            "    def __init__(self, tok):\n"
            "        self.next_token = tok\n"
            "def drive(cache, tok):\n"
            "    rec = Holder(cache)\n"
            "    cache, t = step(cache, tok)\n"
            "    rec.steps = 2\n"  # attribute STORE: no buffer read
            "    return t\n"
        ),
    })
    assert findings == []


def test_donation_alias_shed_before_call_clean(tmp_path):
    """A capture re-bound to something else BEFORE the donating call no
    longer references the donated buffer."""
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/batch.py": DONATING,
        "gofr_tpu/serving/engine.py": (
            "from gofr_tpu.serving.batch import step\n"
            "def drive(cache, tok, other):\n"
            "    keep = cache\n"
            "    keep = other\n"
            "    cache, t = step(cache, tok)\n"
            "    return keep + 1\n"
        ),
    })
    assert findings == []


# ----------------------------------------------------------- retrace hazards
def test_retrace_branch_on_traced_param(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/batch.py": (
            "from functools import partial\n"
            "import jax\n"
            "@partial(jax.jit)\n"
            "def decode(x, flag):\n"
            "    if flag:\n"
            "        return x + 1\n"
            "    return x\n"
        ),
    })
    assert rules_of(findings) == ["retrace-hazard"]
    assert "'flag'" in findings[0].message


def test_retrace_unhashable_static_at_call_site(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/batch.py": (
            "from functools import partial\n"
            "import jax\n"
            "@partial(jax.jit, static_argnums=(1,))\n"
            "def bucketed(x, sizes):\n"
            "    return x\n"
            "def drive(x):\n"
            "    return bucketed(x, [128, 256])\n"
        ),
    })
    assert rules_of(findings) == ["retrace-hazard"]
    assert "static position 1" in findings[0].message


def test_retrace_jit_inside_hot_function(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/engine.py": (
            "import jax\n"
            "def dispatch(fn, x):\n"
            "    return jax.jit(fn)(x)\n"
        ),
    })
    assert rules_of(findings) == ["retrace-hazard"]
    assert "fresh wrapper" in findings[0].message


def test_retrace_static_branch_and_shape_inspection_clean(tmp_path):
    findings = lint_tree(tmp_path, {
        "gofr_tpu/serving/batch.py": (
            "from functools import partial\n"
            "import jax\n"
            "@partial(jax.jit, static_argnums=(1,))\n"
            "def decode(x, steps, scale=None):\n"
            "    if steps > 1:\n"          # static: compiles per bucket
            "        x = x * 2\n"
            "    if scale is None:\n"      # identity test: static
            "        scale = 1.0\n"
            "    if x.shape[0] > 4:\n"     # shape: static under tracing
            "        return x[:4] * scale\n"
            "    return x * scale\n"
        ),
    })
    assert findings == []


def test_retrace_outside_zone_clean(tmp_path):
    # same hazard, but not in the decode hot path: not flagged
    findings = lint_tree(tmp_path, {
        "gofr_tpu/models/extra.py": (
            "from functools import partial\n"
            "import jax\n"
            "@partial(jax.jit)\n"
            "def train(x, flag):\n"
            "    if flag:\n"
            "        return x + 1\n"
            "    return x\n"
        ),
    })
    assert findings == []


# ------------------------------------------------------------- JSON output
def test_json_format_and_stable_ids(tmp_path):
    from gofr_tpu.analysis.__main__ import main

    bad = tmp_path / "gofr_tpu" / "serving"
    bad.mkdir(parents=True)
    (bad / "batch.py").write_text(
        "from functools import partial\n"
        "import jax\n"
        "@partial(jax.jit)\n"
        "def decode(x, flag):\n"
        "    if flag:\n"
        "        return x + 1\n"
        "    return x\n"
    )
    import io
    from contextlib import redirect_stdout

    def run_json():
        buf = io.StringIO()
        with redirect_stdout(buf):
            rc = main([
                str(tmp_path / "gofr_tpu"), "--no-ffi", "--format", "json",
                "--no-baseline",
            ])
        return rc, json.loads(buf.getvalue())

    rc1, out1 = run_json()
    rc2, out2 = run_json()
    assert rc1 == rc2 == 1
    assert out1 == out2  # stable across runs
    (finding,) = out1["findings"]
    assert set(finding) == {"id", "rule", "file", "line", "message"}
    assert finding["rule"] == "retrace-hazard"
    assert finding["id"].startswith("retrace-hazard-")


def test_json_clean_exit_zero(tmp_path):
    from gofr_tpu.analysis.__main__ import main

    pkg = tmp_path / "gofr_tpu"
    pkg.mkdir()
    (pkg / "ok.py").write_text("def f():\n    return 1\n")
    import io
    from contextlib import redirect_stdout

    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = main([str(pkg), "--no-ffi", "--format", "json", "--no-baseline"])
    assert rc == 0
    assert json.loads(buf.getvalue())["findings"] == []


# ------------------------------------------------------------ ratchet baseline
def test_baseline_round_trip(tmp_path):
    from gofr_tpu.analysis.__main__ import main

    bad = tmp_path / "gofr_tpu" / "serving"
    bad.mkdir(parents=True)
    src = (
        "from functools import partial\n"
        "import jax\n"
        "@partial(jax.jit)\n"
        "def decode(x, flag):\n"
        "    if flag:\n"
        "        return x + 1\n"
        "    return x\n"
    )
    (bad / "batch.py").write_text(src)
    baseline = tmp_path / "baseline.json"
    args = [str(tmp_path / "gofr_tpu"), "--no-ffi", "--baseline", str(baseline)]

    # finding blocks before the baseline exists
    assert main(args) == 1
    # record it: subsequent runs pass, the ratchet holds the line
    assert main(args + ["--update-baseline"]) == 0
    assert main(args) == 0
    data = json.loads(baseline.read_text())
    assert data["version"] == baseline_io.BASELINE_VERSION
    assert len(data["findings"]) == 1
    # --no-baseline still reports it
    assert main(args + ["--no-baseline"]) == 1

    # a NEW finding is not covered: the build blocks again
    (bad / "batch.py").write_text(
        src + "def dispatch(fn, x):\n    return jax.jit(fn)(x)\n"
    )
    assert main(args) == 1

    # fixing everything leaves a stale baseline harmless
    (bad / "batch.py").write_text("def f():\n    return 1\n")
    assert main(args) == 0


def test_partial_update_preserves_uncovered_baseline_entries(tmp_path):
    """--update-baseline over a subset must not erase entries for files
    the run never looked at."""
    from gofr_tpu.analysis.__main__ import main

    pkg = tmp_path / "gofr_tpu" / "serving"
    pkg.mkdir(parents=True)
    hazard = (
        "from functools import partial\n"
        "import jax\n"
        "@partial(jax.jit)\n"
        "def decode(x, flag):\n"
        "    if flag:\n"
        "        return x + 1\n"
        "    return x\n"
    )
    (pkg / "batch.py").write_text(hazard)
    (pkg / "engine.py").write_text(hazard)
    baseline = tmp_path / "baseline.json"

    # record both files' findings
    assert main([
        str(tmp_path / "gofr_tpu"), "--no-ffi",
        "--baseline", str(baseline), "--update-baseline",
    ]) == 0
    assert len(json.loads(baseline.read_text())["findings"]) == 2

    # update over ONE file only: the other file's entry must survive
    assert main([
        str(pkg / "batch.py"), "--no-ffi",
        "--baseline", str(baseline), "--update-baseline",
    ]) == 0
    keys = json.loads(baseline.read_text())["findings"]
    assert any("engine.py" in k for k in keys), keys
    # ...and the whole tree still passes against the merged baseline
    assert main([
        str(tmp_path / "gofr_tpu"), "--no-ffi", "--baseline", str(baseline),
    ]) == 0


def test_file_only_update_preserves_cross_file_rule_entries(tmp_path):
    """On a file-only subset, finalize() never runs, so cross-file rules
    (mesh-axis-unknown, use-after-donation, ...) produce no findings —
    their baseline entries must survive the update."""
    from gofr_tpu.analysis.__main__ import main

    pkg = tmp_path / "gofr_tpu" / "parallel"
    pkg.mkdir(parents=True)
    (pkg / "mesh.py").write_text(MESH_DECL)
    (pkg / "rules.py").write_text(
        "from jax.sharding import PartitionSpec as P\n"
        'SPEC = P("model", None)\n'
    )
    baseline = tmp_path / "baseline.json"
    # full-tree update records the mesh-axis-unknown finding
    assert main([
        str(tmp_path / "gofr_tpu"), "--no-ffi",
        "--baseline", str(baseline), "--update-baseline",
    ]) == 0
    before = json.loads(baseline.read_text())["findings"]
    assert any(k.startswith("mesh-axis-unknown|") for k in before)
    # file-only update over the SAME file must not erase the entry
    assert main([
        str(pkg / "rules.py"), "--no-ffi",
        "--baseline", str(baseline), "--update-baseline",
    ]) == 0
    after = json.loads(baseline.read_text())["findings"]
    assert after == before
    assert main([
        str(tmp_path / "gofr_tpu"), "--no-ffi", "--baseline", str(baseline),
    ]) == 0


def test_baseline_counts_per_key(tmp_path):
    f = Finding("r", "a.py", 3, "m")
    g = Finding("r", "a.py", 9, "m")  # same key, different line
    baseline = {"r|a.py|m": 1}
    blocking, baselined = baseline_io.apply_baseline([f, g], baseline)
    assert baselined == 1 and len(blocking) == 1


def test_committed_baseline_is_empty():
    """The repo lints clean; the committed ratchet floor must stay empty
    (new findings are fixed or suppressed inline, never baselined)."""
    path = baseline_io.default_baseline_path()
    assert baseline_io.load_baseline(path) == {}


# ---------------------------------------------------------------- real tree
def test_real_tree_clean_under_shardcheck():
    """Acceptance bar: the shardcheck rules exit clean on the repo (mesh
    vocabulary consistent, no use-after-donation, no retrace hazards)."""
    findings = run_rules([os.path.join(REPO_ROOT, "gofr_tpu")], default_rules())
    shard = [
        f for f in findings
        if f.rule in (
            "mesh-axis-unknown", "collective-unmapped",
            "use-after-donation", "retrace-hazard",
        )
    ]
    assert shard == [], "\n".join(f.render() for f in shard)
