#!/usr/bin/env python
"""Bench regression gate: compare a fresh bench artifact against the
committed BENCH_r*.json trajectory and fail loudly on a same-platform
headline regression.

Usage:
    python scripts/bench_gate.py BENCH_new.json [--root DIR]
                                 [--threshold 0.10] [--pattern 'BENCH_r*.json']

Exit status: 0 = pass / no comparable baseline, 1 = regression beyond
threshold, 2 = unreadable input. Prints exactly one JSON verdict line.

Comparability rule: a prior artifact gates a fresh one only when BOTH
its platform and its measured config match (`extras.platform` /
`extras.config`) — "the 757M flagship on a v5e got slower" is a
regression; a number from another platform or config is not a baseline
for it. The fresh value is
compared against the BEST comparable prior (not the latest): a slow
drift across rounds must not ratchet the baseline down.

bench.py embeds this gate's verdict in every fresh measurement's
`extras.bench_gate`, so round artifacts self-report regressions; CI or
the watcher can also run it standalone against a new artifact file.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Any, Dict, List, Optional

DEFAULT_THRESHOLD = 0.10


def _unwrap(artifact: Any) -> Dict[str, Any]:
    """The driver's round artifacts wrap the bench JSON line under
    "parsed" (next to n/cmd/rc/tail); accept both shapes."""
    if isinstance(artifact, dict) and isinstance(
        artifact.get("parsed"), dict
    ):
        return artifact["parsed"]
    return artifact if isinstance(artifact, dict) else {}


def _comparable(artifact: Dict[str, Any]) -> bool:
    """A trajectory entry that can serve as a baseline: a real number
    with a platform/config identity and no error."""
    if not isinstance(artifact, dict) or artifact.get("error"):
        return False
    value = artifact.get("value")
    if not isinstance(value, (int, float)) or value <= 0:
        return False
    extras = artifact.get("extras", {})
    return bool(extras.get("platform")) and bool(extras.get("config"))


def load_trajectory(
    root: str, pattern: str = "BENCH_r*.json"
) -> List[Dict[str, Any]]:
    """Committed round artifacts, sorted by name (round order)."""
    out: List[Dict[str, Any]] = []
    for path in sorted(glob.glob(os.path.join(root, pattern))):
        try:
            with open(path) as f:
                artifact = _unwrap(json.load(f))
        except (OSError, ValueError):
            continue
        if artifact:
            artifact["_round"] = os.path.basename(path)
            out.append(artifact)
    return out


def gate(
    fresh: Dict[str, Any],
    trajectory: List[Dict[str, Any]],
    threshold: float = DEFAULT_THRESHOLD,
) -> Dict[str, Any]:
    """Verdict dict for `fresh` against `trajectory`.

    verdict: "pass" | "fail" | "no_baseline" (nothing comparable) |
    "not_comparable" (the fresh artifact itself has no identity/value).
    """
    verdict: Dict[str, Any] = {"threshold": threshold}
    fresh = _unwrap(fresh)
    if not _comparable(fresh):
        verdict["verdict"] = "not_comparable"
        verdict["reason"] = "fresh artifact has no usable value/platform/config"
        return verdict
    extras = fresh.get("extras", {})
    platform, config = extras.get("platform"), extras.get("config")
    peers = [
        a
        for a in trajectory
        if _comparable(a)
        and a["extras"].get("platform") == platform
        and a["extras"].get("config") == config
    ]
    verdict["platform"], verdict["config"] = platform, config
    verdict["compared"] = len(peers)
    if not peers:
        verdict["verdict"] = "no_baseline"
        return verdict
    best = max(peers, key=lambda a: a["value"])
    ratio = float(fresh["value"]) / float(best["value"])
    verdict["best_prior"] = {
        "round": best.get("_round"),
        "value": best["value"],
    }
    verdict["ratio"] = round(ratio, 4)
    verdict["verdict"] = "fail" if ratio < 1.0 - threshold else "pass"
    if verdict["verdict"] == "fail":
        verdict["reason"] = (
            f"{config}@{platform} regressed to {ratio:.2%} of "
            f"{best.get('_round')} ({fresh['value']} vs {best['value']})"
        )
    return verdict


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("fresh", help="fresh bench artifact (JSON file)")
    ap.add_argument(
        "--root",
        default=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        help="directory holding the BENCH_r*.json trajectory",
    )
    ap.add_argument("--threshold", type=float, default=DEFAULT_THRESHOLD)
    ap.add_argument("--pattern", default="BENCH_r*.json")
    args = ap.parse_args(argv)
    try:
        with open(args.fresh) as f:
            fresh = json.load(f)
    except (OSError, ValueError) as e:
        print(json.dumps({"verdict": "error", "reason": str(e)}))
        return 2
    verdict = gate(
        fresh, load_trajectory(args.root, args.pattern), args.threshold
    )
    print(json.dumps(verdict))
    return 1 if verdict["verdict"] == "fail" else 0


if __name__ == "__main__":
    sys.exit(main())
