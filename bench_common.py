"""Shared child-subprocess runner for the bench harnesses.

bench.py and bench_ops.py parents stay off jax (a chip belongs to one
process at a time) and run one child at a time with a timeout, recovering
exactly one validated JSON payload from the child's stdout. One
implementation here so the behavior can't drift between them.
"""

from __future__ import annotations

import json
import subprocess
from typing import Callable, Dict, List, Optional, Tuple


def enable_compile_cache() -> str:
    """Bench children and sweeps call this before their first compile;
    the rule itself (env var wins, else <checkout>/.jax_cache) lives in
    luminaai_tpu.utils.environment.configure_compile_cache."""
    from luminaai_tpu.utils.environment import configure_compile_cache

    return configure_compile_cache()


def run_child(
    cmd: List[str],
    timeout: int,
    validate: Callable[[Dict], bool],
    label: str,
    env: Optional[Dict[str, str]] = None,
    cwd: Optional[str] = None,
) -> Tuple[Optional[Dict], str]:
    """Run cmd; return (payload | None, diagnostic).

    The payload is the LAST stdout line that parses as a JSON object and
    passes `validate` — stray JSON-ish runtime log lines are skipped.
    """
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=timeout,
            env=env, cwd=cwd,
        )
    except subprocess.TimeoutExpired:
        return None, f"{label}: timeout after {timeout}s"
    for line in reversed(proc.stdout.strip().splitlines()):
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            parsed = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(parsed, dict) and validate(parsed):
            return parsed, f"{label}: ok"
    return None, f"{label}: rc={proc.returncode} stderr={proc.stderr[-500:]!r}"
