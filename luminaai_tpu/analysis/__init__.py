"""JAX-aware static analysis: AST lint rules + abstract-eval auditors.

Three bug classes this repo has shipped are mechanically detectable
before anything runs:

  - `shard_map` reached around `parallel/mesh.shard_map`, the one entry
    point repo code calls;
  - silent recompiles that `train_recompiles_total` only counts after
    the fact (ROADMAP item 5's per-variant recompile surface);
  - sharding-annotation gaps and host syncs inside jitted hot paths,
    which GSPMD "annotate, don't fork" discipline treats as bugs.

`astlint` is the source-level layer (rule ids LX001..LX008, inline
waivers, JSON + human output); `jaxpr_audit` is the abstract-eval layer
(recompile-surface enumerator, sharding-coverage auditor, host-transfer
detector). Both are fronted by `lumina analyze` and run as a blocking
CI step. See docs/static_analysis.md for the rule catalogue.
"""

from luminaai_tpu.analysis.astlint import (  # noqa: F401
    ALL_RULES,
    Finding,
    findings_to_json,
    format_findings,
    lint_paths,
    lint_source,
)
