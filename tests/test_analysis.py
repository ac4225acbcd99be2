"""Static-analysis subsystem tests (luminaai_tpu/analysis/).

Three contracts, per ISSUE 6's acceptance criteria:

  1. every astlint rule FIRES on its golden known-bad fixture and stays
     SILENT on the repo's own package tree (waivers included);
  2. the abstract-eval auditors pin today's recompile surface (the
     ROADMAP-item-5 baseline the unified-forward refactor drives down)
     and full sharding coverage on a CPU mesh;
  3. `lumina analyze` exits 0 on the repo and 1 when a golden violation
     is injected — the CI blocking-step contract.
"""

import ast
import json
import os

import pytest

from luminaai_tpu.analysis import astlint
from luminaai_tpu.analysis.astlint import (
    ALL_RULES,
    findings_to_json,
    lint_paths,
    lint_source,
)

import luminaai_tpu

PKG_DIR = os.path.dirname(os.path.abspath(luminaai_tpu.__file__))
REPO_ROOT = os.path.dirname(PKG_DIR)


# ---------------------------------------------------------------------------
# golden known-bad fixtures: one per rule, each must fire
# ---------------------------------------------------------------------------

GOLDEN_FIXTURES = {
    "LX001": (
        "from jax.experimental.shard_map import shard_map\n"
        "\n"
        "def f(mesh, x):\n"
        "    return shard_map(\n"
        "        lambda v: v, mesh=mesh, in_specs=None, out_specs=None\n"
        "    )(x)\n"
    ),
    "LX002": (
        "import jax\n"
        "import numpy as np\n"
        "\n"
        "@jax.jit\n"
        "def step(state, batch):\n"
        "    loss = (state - batch).sum()\n"
        "    host = loss.item()\n"
        "    arr = np.asarray(batch)\n"
        "    jax.device_get(state)\n"
        "    loss.block_until_ready()\n"
        "    return host, arr\n"
    ),
    "LX003": (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    if x > 0:\n"
        "        y = jnp.log(x)\n"
        "    else:\n"
        "        y = x\n"
        "    msg = f'value was {x}'\n"
        "    return y, msg\n"
    ),
    "LX004": (
        "import time\n"
        "import jax\n"
        "\n"
        "@jax.jit\n"
        "def train_step(state, batch):\n"
        "    t0 = time.time()\n"
        "    return state, t0\n"
    ),
    "LX005": (
        "import jax\n"
        "\n"
        "def sample(shape):\n"
        "    key = jax.random.PRNGKey(0)\n"
        "    a = jax.random.normal(key, shape)\n"
        "    b = jax.random.uniform(key, shape)\n"
        "    return a + b\n"
    ),
    "LX006": (
        "import jax\n"
        "\n"
        "def make_step(model):\n"
        "    def train_step(state, batch):\n"
        "        return state\n"
        "    return jax.jit(train_step)\n"
    ),
    "LX007": (
        "import flax.linen as nn\n"
        "\n"
        "class Block(nn.Module):\n"
        "    features: int = 8\n"
        "    gate_dims: list = [1, 2, 3]\n"
    ),
    "LX008": (
        "def run(f):\n"
        "    try:\n"
        "        return f()\n"
        "    except:\n"
        "        return None\n"
    ),
    "LX009": (
        "def wire(r):\n"
        "    return r.counter(\n"
        "        'tenant_requests_total', 'per-tenant requests',\n"
        "        labelnames=('tenant',),\n"
        "    )\n"
    ),
    "LX010": (
        "import jax\n"
        "\n"
        "def exchange(x):\n"
        "    y = jax.lax.all_to_all(\n"
        "        x, 'expert', split_axis=0, concat_axis=0, tiled=True\n"
        "    )\n"
        "    return jax.lax.ppermute(y, 'expert', [(0, 1), (1, 0)])\n"
    ),
}


@pytest.mark.parametrize("rule_id", sorted(GOLDEN_FIXTURES))
def test_golden_fixture_fires(rule_id):
    findings = lint_source(GOLDEN_FIXTURES[rule_id], f"fixture_{rule_id}.py")
    fired = {f.rule for f in findings}
    assert rule_id in fired, (
        f"{rule_id} must fire on its golden fixture; fired={fired}"
    )
    assert all(not f.waived for f in findings)


def test_every_rule_has_a_golden_fixture():
    assert {r.id for r in ALL_RULES} == set(GOLDEN_FIXTURES)


# ---------------------------------------------------------------------------
# repo silence: the package tree is the CI gate's default scope
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def repo_findings():
    return lint_paths([PKG_DIR], rel_to=REPO_ROOT)


def test_repo_is_clean(repo_findings):
    unwaived = [f for f in repo_findings if not f.waived]
    assert not unwaived, astlint.format_findings(unwaived)


@pytest.mark.parametrize("rule_id", sorted(GOLDEN_FIXTURES))
def test_rule_silent_on_repo(repo_findings, rule_id):
    hits = [f for f in repo_findings if f.rule == rule_id and not f.waived]
    assert not hits, astlint.format_findings(hits)


def test_environment_no_direct_shard_map_import(repo_findings):
    """Regression for the day-one LX001 violation: connectivity_probe
    imported jax.experimental.shard_map directly instead of the one
    parallel/mesh.shard_map entry point. Both the lint
    view and the raw AST must agree it is gone."""
    env_path = os.path.join(PKG_DIR, "utils", "environment.py")
    with open(env_path) as fh:
        tree = ast.parse(fh.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            assert node.module != "jax.experimental.shard_map", (
                f"environment.py:{node.lineno} reintroduced the direct "
                "experimental import; use parallel/mesh.shard_map"
            )
    hits = [
        f for f in repo_findings
        if f.rule == "LX001" and f.path.endswith("environment.py")
    ]
    assert not hits


# ---------------------------------------------------------------------------
# waivers
# ---------------------------------------------------------------------------


def test_inline_waiver_applies_with_reason():
    src = (
        "import jax\n"
        "\n"
        "def sample(shape):\n"
        "    key = jax.random.PRNGKey(0)\n"
        "    a = jax.random.normal(key, shape)\n"
        "    b = jax.random.uniform(key, shape)"
        "  # lumina: disable=LX005 -- intentional identical draws\n"
        "    return a + b\n"
    )
    findings = lint_source(src, "waived.py")
    assert len(findings) == 1
    assert findings[0].waived
    assert findings[0].waiver_reason == "intentional identical draws"


def test_waiver_for_other_rule_does_not_apply():
    src = GOLDEN_FIXTURES["LX008"].replace(
        "    except:", "    except:  # lumina: disable=LX001 -- wrong id"
    )
    findings = lint_source(src, "waived.py")
    assert [f.rule for f in findings] == ["LX008"]
    assert not findings[0].waived


def test_syntax_error_is_a_finding_not_a_pass():
    findings = lint_source("def broken(:\n", "broken.py")
    assert [f.rule for f in findings] == ["LX000"]


# ---------------------------------------------------------------------------
# jit-context detection details the rules depend on
# ---------------------------------------------------------------------------


def test_partial_keyword_bindings_are_static():
    """Keyword args bound through functools.partial are build-time
    Python values: branching on them is legal (ring_attention's
    `causal` pattern must stay clean)."""
    src = (
        "import functools\n"
        "import jax\n"
        "\n"
        "def body(x, *, causal):\n"
        "    if causal:\n"
        "        return x\n"
        "    return -x\n"
        "\n"
        "def run(xs):\n"
        "    return jax.lax.scan(\n"
        "        functools.partial(body, causal=True), xs, None\n"
        "    )\n"
    )
    assert not lint_source(src, "p.py")


def test_scan_body_is_a_traced_context():
    src = (
        "import jax\n"
        "\n"
        "def body(carry, x):\n"
        "    host = x.item()\n"
        "    return carry, host\n"
        "\n"
        "def run(xs):\n"
        "    return jax.lax.scan(body, 0, xs)\n"
    )
    assert [f.rule for f in lint_source(src, "s.py")] == ["LX002"]


def test_static_argnames_suppresses_tracer_branch():
    src = (
        "import jax\n"
        "from functools import partial\n"
        "\n"
        "@partial(jax.jit, static_argnames=('mode',))\n"
        "def apply_fn(x, mode):\n"
        "    if mode:\n"
        "        return x\n"
        "    return -x\n"
    )
    assert not lint_source(src, "s.py")


def test_call_form_static_argnums_suppresses_tracer_branch():
    # jax.jit(f, static_argnums=...) over a bare name must resolve the
    # argnum indices against f's local def — a branch on the static
    # param is NOT a tracer branch.
    src = (
        "import jax\n"
        "\n"
        "def apply_fn(x, mode):\n"
        "    if mode:\n"
        "        return x\n"
        "    return -x\n"
        "\n"
        "fast = jax.jit(apply_fn, static_argnums=(1,))\n"
    )
    assert not lint_source(src, "s.py")


def test_key_consumed_once_per_exclusive_branch_is_clean():
    # if/else branches are mutually exclusive at runtime: one
    # consumption per branch is not reuse.
    src = (
        "import jax\n"
        "\n"
        "def sample(gaussian, shape):\n"
        "    key = jax.random.PRNGKey(0)\n"
        "    if gaussian:\n"
        "        a = jax.random.normal(key, shape)\n"
        "    else:\n"
        "        a = jax.random.uniform(key, shape)\n"
        "    return a\n"
    )
    assert not lint_source(src, "k.py")


def test_key_consumed_in_branch_then_after_fires():
    # ...but a consumption AFTER the if still sees a consumed key.
    src = (
        "import jax\n"
        "\n"
        "def sample(flag, shape):\n"
        "    key = jax.random.PRNGKey(0)\n"
        "    if flag:\n"
        "        a = jax.random.normal(key, shape)\n"
        "    else:\n"
        "        a = jax.random.uniform(key, shape)\n"
        "    b = jax.random.normal(key, shape)\n"
        "    return a + b\n"
    )
    findings = lint_source(src, "k.py")
    assert [f.rule for f in findings] == ["LX005"]
    assert findings[0].line == 9  # the post-if consumption, not a branch


def test_key_reuse_findings_land_in_source_order():
    # Within one statement, the FIRST call in source order is the fresh
    # consumption and later calls are the reuses — waivers key on the
    # flagged line, so order is contract.
    src = (
        "import jax\n"
        "\n"
        "def params(shape):\n"
        "    key = jax.random.PRNGKey(0)\n"
        "    return (jax.random.normal(key, shape),\n"
        "            jax.random.normal(key, shape),\n"
        "            jax.random.normal(key, shape))\n"
    )
    findings = lint_source(src, "k.py")
    assert [f.rule for f in findings] == ["LX005", "LX005"]
    assert [f.line for f in findings] == [6, 7]


def test_iter_python_files_skips_hidden_and_vendored_trees(tmp_path):
    (tmp_path / "pkg").mkdir()
    (tmp_path / "pkg" / "ok.py").write_text("x = 1\n")
    for vendor in (".venv", ".git", "node_modules", "site-packages"):
        (tmp_path / vendor).mkdir()
        (tmp_path / vendor / "third_party.py").write_text("except\n")
    found = list(astlint.iter_python_files([str(tmp_path)]))
    assert found == [str(tmp_path / "pkg" / "ok.py")]


def test_key_rotation_idiom_is_clean():
    src = (
        "import jax\n"
        "\n"
        "def sample(n, shape):\n"
        "    key = jax.random.PRNGKey(0)\n"
        "    outs = []\n"
        "    for _ in range(n):\n"
        "        key, sub = jax.random.split(key)\n"
        "        outs.append(jax.random.normal(sub, shape))\n"
        "    return outs\n"
    )
    assert not lint_source(src, "k.py")


def test_key_reuse_across_loop_iterations_fires():
    src = (
        "import jax\n"
        "\n"
        "def sample(n, shape):\n"
        "    key = jax.random.PRNGKey(0)\n"
        "    outs = []\n"
        "    for _ in range(n):\n"
        "        outs.append(jax.random.normal(key, shape))\n"
        "    return outs\n"
    )
    assert [f.rule for f in lint_source(src, "k.py")] == ["LX005"]


def test_donated_step_jit_is_clean():
    src = (
        "import jax\n"
        "\n"
        "def make(model):\n"
        "    def train_step(state, batch):\n"
        "        return state\n"
        "    return jax.jit(train_step, donate_argnums=(0,))\n"
    )
    assert not lint_source(src, "d.py")


@pytest.mark.parametrize(
    "decorator",
    ["@jax.jit", "@partial(jax.jit)",
     "@partial(jax.jit, static_argnames=('n',))"],
)
def test_lx006_fires_on_decorator_forms(decorator):
    """Review-found gap: decorator-form jits must be covered, not just
    jit(fn) call forms."""
    src = (
        "import jax\n"
        "from functools import partial\n"
        f"{decorator}\n"
        "def train_step(state, batch, n=1):\n"
        "    return state\n"
    )
    assert "LX006" in {f.rule for f in lint_source(src, "d.py")}


def test_lx006_decorator_with_donation_is_clean():
    src = (
        "import jax\n"
        "from functools import partial\n"
        "@partial(jax.jit, donate_argnums=(0,))\n"
        "def train_step(state, batch):\n"
        "    return state\n"
    )
    assert not lint_source(src, "d.py")


# ---------------------------------------------------------------------------
# JSON / human output
# ---------------------------------------------------------------------------


def test_findings_to_json_shape():
    findings = lint_source(GOLDEN_FIXTURES["LX001"], "bad.py")
    doc = findings_to_json(findings)
    assert doc["summary"]["total"] == len(findings)
    assert doc["summary"]["unwaived"] == len(findings)
    assert doc["summary"]["by_rule"].get("LX001", 0) >= 1
    assert set(doc["rules"]) == {r.id for r in ALL_RULES}
    json.dumps(doc)  # must be serializable as-is


# ---------------------------------------------------------------------------
# abstract-eval auditors
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def surface_report():
    from luminaai_tpu.analysis.jaxpr_audit import enumerate_recompile_surface
    from luminaai_tpu.monitoring.telemetry import MetricsRegistry

    registry = MetricsRegistry()
    report = enumerate_recompile_surface(registry=registry)
    return report, registry


def test_recompile_surface_pins_current_counts(surface_report):
    """THE baseline number for ROADMAP item 5: the enumerated scenarios
    compile to 7 distinct executables (train: scan on/off x gmm/einsum
    = 4; decode: 2 prompt-length scenarios sharing ONE chunked-prefill
    executable + scalar-offset + batched cache_index = 3). The LaneMeta
    unification took decode from 4 to 3 by collapsing the prefill
    bucket ladder; further reductions lower these pins deliberately. If
    a change RAISES them, a new forked variant slipped into the hot
    path. Still 7 after ISSUE 34: the chunk program it deleted was the
    SERVING path's (never enumerated here: the prefill scenarios are
    generate()'s), and a served chunk now rides the batched-cache_index
    executable, whose one signature covers lanes and chunk rows."""
    report, _ = surface_report
    train = report["programs"]["train"]
    decode = report["programs"]["decode"]
    assert len(train["variants"]) == 4
    assert train["distinct_signatures"] == 4
    assert len(decode["variants"]) == 4
    assert decode["distinct_signatures"] == 3
    assert report["total_variants"] == 8
    assert report["total_distinct"] == 7


def test_recompile_surface_hot_paths_have_no_host_transfers(surface_report):
    report, _ = surface_report
    assert report["host_transfer_ops"] == {}
    for prog in report["programs"].values():
        for v in prog["variants"]:
            assert v["host_transfer_ops"] == {}, v["variant"]


def test_recompile_surface_exports_gauges(surface_report):
    # The registry snapshot format is exercised in test_telemetry; here
    # just assert both gauge families landed in the same registry.
    _, registry = surface_report
    text = json.dumps(registry.snapshot())
    assert "analysis_recompile_surface" in text
    assert "analysis_host_transfer_ops" in text


def test_prefill_scenarios_share_one_chunked_executable(surface_report):
    """Chunked prefill feeds every prompt length through one fixed-chunk
    step: the enumerated prompt-length scenarios must collapse to a
    SINGLE signature (the inversion of the old per-bucket pin — under
    the bucket ladder these were two executables). These are
    generate()'s; a SERVED prompt's chunks have no executable at all
    (they ride the tick: tests/test_tick_program.py pins that the
    decoder builds step programs and nothing else for them)."""
    report, _ = surface_report
    sigs = {
        v["variant"]: v["signature"]
        for v in report["programs"]["decode"]["variants"]
        if v["variant"].startswith("prefill/")
    }
    assert len(sigs) == 2
    assert len(set(sigs.values())) == 1
    tick = [
        v for v in report["programs"]["decode"]["variants"]
        if v["variant"] == "decode/batched_cache_index"
    ]
    assert len(tick) == 1 and tick[0]["signature"] not in sigs.values()


def test_sharding_coverage_full_on_cpu_mesh():
    from luminaai_tpu.analysis.jaxpr_audit import audit_sharding_coverage
    from luminaai_tpu.monitoring.telemetry import MetricsRegistry

    registry = MetricsRegistry()
    out = audit_sharding_coverage(registry=registry)
    assert out["total_leaves"] > 0
    assert out["unannotated_leaves"] == 0, out["flagged"]
    assert out["coverage"] == 1.0
    assert "sharding_annotation_coverage" in json.dumps(registry.snapshot())


def test_host_transfer_detector_fires_on_callbacks():
    import jax
    import jax.numpy as jnp

    from luminaai_tpu.analysis.jaxpr_audit import detect_host_transfers

    def noisy(x):
        jax.debug.print("x sum {s}", s=x.sum())
        return x * 2

    closed = jax.make_jaxpr(noisy)(jnp.ones((4,)))
    counts = detect_host_transfers(closed)
    assert counts, "debug callback must be detected"
    assert sum(counts.values()) >= 1


def test_host_transfer_detector_clean_on_pure_fn():
    import jax
    import jax.numpy as jnp

    from luminaai_tpu.analysis.jaxpr_audit import detect_host_transfers

    closed = jax.make_jaxpr(lambda x: (x @ x.T).sum())(jnp.ones((4, 4)))
    assert detect_host_transfers(closed) == {}


# ---------------------------------------------------------------------------
# comms auditor (the recompile-surface pattern, applied to collectives)
# ---------------------------------------------------------------------------


def test_enumerate_collectives_census_and_stage_classification():
    """Unit contract on a hand-built shard_map body: counts, axes,
    payload bytes, and the contiguous-vs-strided stage classifier."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from luminaai_tpu.analysis.jaxpr_audit import enumerate_collectives
    from luminaai_tpu.parallel.mesh import all_to_all, shard_map

    mesh = Mesh(np.array(jax.devices()[:4]), ("expert",))

    def body(x):  # x [4, 2, 8] per shard
        flat = all_to_all(x, "expert", split_axis=0, concat_axis=0,
                          tiled=True)
        r = x.reshape(2, 2, 2, 8)
        ici = all_to_all(r, "expert", split_axis=1, concat_axis=1,
                         tiled=True,
                         axis_index_groups=[[0, 1], [2, 3]])
        dcn = all_to_all(ici, "expert", split_axis=0, concat_axis=0,
                         tiled=True,
                         axis_index_groups=[[0, 2], [1, 3]])
        return jax.lax.psum(
            flat.sum() + dcn.sum(), "expert"
        )

    closed = jax.make_jaxpr(
        shard_map(
            body, mesh=mesh, in_specs=P("expert"), out_specs=P(),
            check_vma=False,
        )
    )(jnp.ones((16, 2, 8), jnp.float32))
    census = enumerate_collectives(closed)
    assert census["counts"] == {"all_to_all": 3, "psum": 1}
    stages = sorted(
        rec["stage"] for rec in census["ops"]
        if rec["primitive"] == "all_to_all"
    )
    assert stages == ["dcn", "flat", "ici"]
    for rec in census["ops"]:
        if rec["primitive"] == "all_to_all":
            assert rec["payload_bytes"] == 4 * 2 * 8 * 4  # per-shard f32
            assert rec["axes"] == ("expert",)


def test_a2a_stage_classifier_degenerate_factorings():
    """Review fix: with ici == 1 (one expert chip per host) the single
    stage-2 rail is CONTIGUOUS [0..dcn-1] — it must classify as 'dcn'
    (every byte crosses hosts), and the singleton stage-1 groups as
    'ici'. The strided/contiguous signature alone would invert the
    auditor's one job for that legal config."""
    from luminaai_tpu.analysis.jaxpr_audit import _a2a_stage
    from luminaai_tpu.parallel.expert_dispatch import hierarchical_groups

    g1, g2 = hierarchical_groups(4, 4)  # ici == 1
    assert _a2a_stage({"axis_index_groups": g1}) == "ici"
    assert _a2a_stage({"axis_index_groups": g2}) == "dcn"
    g1, g2 = hierarchical_groups(8, 2)  # the common shape
    assert _a2a_stage({"axis_index_groups": g1}) == "ici"
    assert _a2a_stage({"axis_index_groups": g2}) == "dcn"
    assert _a2a_stage({"axis_index_groups": None}) == "flat"


@pytest.fixture(scope="module")
def ep_dispatch_report():
    from luminaai_tpu.analysis.jaxpr_audit import audit_ep_dispatch

    return audit_ep_dispatch()


def test_ep_dispatch_audit_pins_collective_counts(ep_dispatch_report):
    """Pinned collective counts for the a2a MoE layer program (ep8 =
    dcn2 × ici4, overlap chunks 2): 1 counts exchange + 1 stage-1 +
    chunks stage-2 dispatch + chunks stage-2 combine + 1 stage-1
    combine = 7 all_to_alls; 3 psums (tokens_per_expert + the two
    routed-token stats — NO full-activation psum, that's the point).
    The replicated gmm baseline: 2 psums (full token outputs + counts).
    A change that RAISES these means a collective slipped into the hot
    path; one that removes the stage split breaks the dcn audit."""
    rep = ep_dispatch_report
    assert rep["available"], rep
    assert rep["a2a"]["counts"] == {"all_to_all": 7, "psum": 3}
    assert rep["replicated_gather"]["counts"] == {"psum": 2}
    # Stage byte split exists and the flat (counts) exchange is tiny.
    stages = rep["a2a"]["stages"]
    assert stages["ici"] > 0 and stages["dcn"] > 0
    assert stages["flat"] < 1024  # the int32 counts matrix


def test_ep_dispatch_audit_dcn_bytes_strictly_below_gather(
    ep_dispatch_report,
):
    """THE acceptance pin (mirrored in CI via extras.ep_dispatch): the
    a2a path's dcn-crossing payload bytes are strictly below the
    replicated gather's on the same mesh and routing shape."""
    rep = ep_dispatch_report
    assert rep["available"], rep
    assert 0 < rep["a2a_dcn_bytes"] < rep["gather_dcn_bytes"]
    assert rep["a2a_below_gather"] is True
    # And the static DispatchPlan agrees with the traced direction.
    plan = rep["plan"]
    assert plan["a2a_dcn_bytes"] > 0
    assert plan["a2a_dcn_bytes"] < plan["baseline_dcn_bytes"]


# ---------------------------------------------------------------------------
# `lumina analyze` CLI contract (the CI blocking step)
# ---------------------------------------------------------------------------


def _run_analyze(argv):
    from luminaai_tpu.cli import main

    return main(["analyze", "--no-audit", *argv])


def test_cli_analyze_repo_exits_zero(capsys):
    assert _run_analyze([]) == 0
    assert "clean" in capsys.readouterr().out


def test_cli_analyze_injected_violation_fails(tmp_path, capsys):
    bad = tmp_path / "injected.py"
    bad.write_text(GOLDEN_FIXTURES["LX001"])
    assert _run_analyze([str(tmp_path)]) == 1
    assert "LX001" in capsys.readouterr().out


@pytest.mark.parametrize("rule_id", sorted(GOLDEN_FIXTURES))
def test_cli_analyze_fails_on_every_golden_violation(
    tmp_path, rule_id, capsys
):
    """The acceptance contract: injecting ANY golden fixture violation
    into the analyzed tree makes the CI step fail."""
    bad = tmp_path / f"injected_{rule_id.lower()}.py"
    bad.write_text(GOLDEN_FIXTURES[rule_id])
    assert _run_analyze([str(tmp_path)]) == 1
    capsys.readouterr()


def test_cli_analyze_json_document(tmp_path, capsys):
    bad = tmp_path / "injected.py"
    bad.write_text(GOLDEN_FIXTURES["LX002"])
    code = _run_analyze(["--json", str(tmp_path)])
    doc = json.loads(capsys.readouterr().out)
    assert code == 1
    assert doc["exit_code"] == 1
    assert doc["summary"]["unwaived"] >= 1
    assert any(f["rule"] == "LX002" for f in doc["findings"])


def test_cli_analyze_baseline_accepts_legacy_findings(tmp_path, capsys):
    bad = tmp_path / "legacy.py"
    bad.write_text(GOLDEN_FIXTURES["LX001"])
    baseline = tmp_path / "baseline.json"

    # write-baseline captures the current findings...
    code = _run_analyze(
        ["--write-baseline", str(baseline), str(tmp_path)]
    )
    assert code == 1  # first run still fails: nothing accepted yet
    accepted = json.loads(baseline.read_text())["accepted"]
    assert sum(accepted.values()) == 1
    capsys.readouterr()

    # ...and a rerun against that baseline passes, with the absorbed
    # finding explicitly tagged so the listing can't read as a failure.
    assert _run_analyze(["--baseline", str(baseline), str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "[baselined" in out
    assert "0 unwaived" in out
    worse = tmp_path / "new_violation.py"
    worse.write_text(GOLDEN_FIXTURES["LX008"])
    assert _run_analyze(["--baseline", str(baseline), str(tmp_path)]) == 1
    capsys.readouterr()


def test_cli_analyze_waived_finding_passes(tmp_path, capsys):
    src = GOLDEN_FIXTURES["LX008"].replace(
        "    except:",
        "    except:  # lumina: disable=LX008 -- fixture: probing is best-effort",
    )
    (tmp_path / "waived.py").write_text(src)
    assert _run_analyze([str(tmp_path)]) == 0
    assert "waived" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# LX009 — tenant-label budget semantics (prefix cache / QoS series)
# ---------------------------------------------------------------------------
def test_lx009_budgeted_tenant_families_are_silent():
    src = (
        "def wire(r, n):\n"
        "    tk = dict(labelnames=('tenant',), max_label_values=n)\n"
        "    r.counter('tenant_requests_total', 'h', **tk)\n"
        "    return r.gauge('tenant_prefix_cache_pages', 'h',\n"
        "                   labelnames=('tenant',), max_label_values=n)\n"
    )
    assert not [f for f in lint_source(src, "k.py") if f.rule == "LX009"]


def test_lx009_fires_on_unbudgeted_dict_idiom():
    # The shared-kwargs dict form (tk = dict(...)) must be checked at
    # the dict, where the budget omission actually lives.
    src = (
        "def wire(r):\n"
        "    tk = dict(labelnames=('tenant',))\n"
        "    r.counter('tenant_requests_total', 'h', **tk)\n"
    )
    assert [f.rule for f in lint_source(src, "k.py")] == ["LX009"]
    literal = (
        "def wire(r):\n"
        "    tk = {'labelnames': ('tenant',)}\n"
        "    r.counter('tenant_requests_total', 'h', **tk)\n"
    )
    assert [f.rule for f in lint_source(literal, "k.py")] == ["LX009"]


def test_lx009_ignores_non_tenant_labels():
    src = (
        "def wire(r):\n"
        "    return r.counter('serve_http_requests_total', 'h',\n"
        "                     labelnames=('route', 'code'))\n"
    )
    assert not [f for f in lint_source(src, "k.py") if f.rule == "LX009"]
