"""BENCHMARK.json and the data files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by name:

    configs/<config>.json        sizes as run, source, reduced, departures
    traffic/<traffic>.json       kind + parameters of the general generator
    layer_metrics/<base>.json    how a per-layer metric is read

`<base>` is a metric's name up to its first '.', so `decode_step_ms.chat`
and `decode_step_ms.batch` share one reader: which cells report a metric
is BENCHMARK.json's `workloads` key alone. No module here imports jax.
"""

from __future__ import annotations

import json
import os
import re
from typing import Any, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
# `trace_in_run`: the driver passes --trace 0 and --trace 2 (one process
# measures, then traces) and no longer --trace 1.
OPTIONAL_TOP_KEYS = {"trace_in_run"}
WIDTH_WORDS = ("latent", "state_", "head_dim", "expansion",
               "experts_per_tok")


class ManifestError(Exception):
    """BENCHMARK.json or a file it names is wrong."""


def _load(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    return _load(os.path.join(root, "BENCHMARK.json"))


def metric_base(name: str) -> str:
    return name.split(".", 1)[0]


def config_file(name: str) -> str:
    return os.path.join(HERE, "configs", f"{name}.json")


def traffic_file(name: str) -> str:
    return os.path.join(HERE, "traffic", f"{name}.json")


def layer_metric_file(name: str) -> str:
    return os.path.join(HERE, "layer_metrics", f"{metric_base(name)}.json")


def _reported_in(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class Cell:
    """One `workloads` entry with the files and metric lists it resolves to."""

    def __init__(self, bench: Dict[str, Any], name: str):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise ManifestError(
                f"no workload {name!r} in BENCHMARK.json (has {sorted(cells)})"
            )
        w = cells[name]
        self.name = name
        self.chips = int(w["chips"])
        self.config_name = w["config"]
        self.traffic_name = w["traffic"]
        self.config = _load(config_file(w["config"]))
        self.traffic = _load(traffic_file(w["traffic"]))
        self.end_to_end = [
            m for m in bench["end_to_end"] if _reported_in(m, name)
        ]
        self.per_layer = [
            m for m in bench["per_layer"] if _reported_in(m, name)
        ]

    def layer_metric_specs(self) -> Dict[str, Dict[str, Any]]:
        return {
            m["name"]: _load(layer_metric_file(m["name"]))
            for m in self.per_layer
        }


def check(bench: Dict[str, Any]) -> List[str]:
    """Every fault found in BENCHMARK.json against the contract and the
    files under benchmark/; empty when sound."""
    bad: List[str] = []
    if not TOP_KEYS <= set(bench) <= TOP_KEYS | OPTIONAL_TOP_KEYS:
        bad.append(f"top-level keys {sorted(bench)} != {sorted(TOP_KEYS)} "
                   f"(+ optional {sorted(OPTIONAL_TOP_KEYS)})")
        return bad
    if not isinstance(bench.get("trace_in_run", False), bool):
        bad.append("trace_in_run is not a boolean")
    if not 1 <= int(bench["run_seconds"]) <= 51:
        bad.append("run_seconds outside 1..51")
    for p in bench["paths"]:
        if p.startswith("/") or ".." in p.split("/"):
            bad.append(f"path {p!r} leaves the repo")
    cfgs = {c["name"]: c for c in bench["configs"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    names = (
        [c["name"] for c in bench["configs"]]
        + [w["name"] for w in bench["workloads"]]
        + [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    )
    for n in names + [w["traffic"] for w in bench["workloads"]]:
        if not NAME_RE.match(n):
            bad.append(f"name {n!r} outside the allowed characters")
    for group in ("configs", "workloads"):
        ns = [x["name"] for x in bench[group]]
        if len(ns) != len(set(ns)):
            bad.append(f"duplicate name in {group}")
    ms = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    if len(ms) != len(set(ms)):
        bad.append("duplicate metric name")
    if "setup_s" not in e2e:
        bad.append("no setup_s among end_to_end")
    for m in bench["end_to_end"] + bench["per_layer"]:
        if not UNIT_RE.match(m["unit"]):
            bad.append(f"unit {m['unit']!r} of {m['name']}")
        if m["better"] not in ("lower", "higher"):
            bad.append(f"better of {m['name']}")
        if m["source"] not in SOURCES:
            bad.append(f"source of {m['name']}")
        for c in m.get("workloads", ()):
            if c not in cells:
                bad.append(f"{m['name']} lists unknown cell {c!r}")
    for m in bench["end_to_end"]:
        if set(m) - {"name", "unit", "better", "bound", "source",
                     "workloads"}:
            bad.append(f"extra key on {m['name']}")
        if not 0 < m["bound"] <= 0.1:
            bad.append(f"bound of {m['name']}")
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"end-to-end source of {m['name']}")
    for m in bench["per_layer"]:
        if set(m) - {"name", "unit", "better", "source", "layer", "moves",
                     "workloads"}:
            bad.append(f"extra key on {m['name']}")
        if m["moves"] not in e2e:
            bad.append(f"{m['name']} moves unknown {m['moves']!r}")
            continue
        for c in m.get("workloads", cells):
            if not _reported_in(e2e[m["moves"]], c):
                bad.append(
                    f"{m['name']} moves {m['moves']}, which cell {c} "
                    "does not report"
                )
        if not os.path.exists(layer_metric_file(m["name"])):
            bad.append(f"no reader file for {m['name']}")
    seen_pairs = set()
    used_cfgs = set()
    for w in bench["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"keys of cell {w['name']}")
        if w["chips"] not in (1, 4):
            bad.append(f"chips of {w['name']}")
        if len(w["why"]) > 200 or "\n" in w["why"] or "\t" in w["why"]:
            bad.append(f"why of {w['name']}")
        if (w["config"], w["traffic"]) in seen_pairs:
            bad.append(f"pair of {w['name']} appears twice")
        seen_pairs.add((w["config"], w["traffic"]))
        used_cfgs.add(w["config"])
        if w["config"] not in cfgs:
            bad.append(f"cell {w['name']} names unknown config")
        elif not os.path.exists(config_file(w["config"])):
            bad.append(f"no config file for {w['config']}")
        if not os.path.exists(traffic_file(w["traffic"])):
            bad.append(f"no traffic file for {w['traffic']}")
        n_e2e = [m for m in bench["end_to_end"]
                 if _reported_in(m, w["name"]) and m["name"] != "setup_s"]
        if not n_e2e:
            bad.append(f"cell {w['name']} reports no end-to-end metric")
        if not any(_reported_in(m, w["name"]) for m in bench["per_layer"]):
            bad.append(f"cell {w['name']} reports no per-layer metric")
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    if len(four) > max(1, len(bench["workloads"]) // 4):
        bad.append("too many four-chip cells")
    for c in bench["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad.append(f"keys of config {c['name']}")
        if c["name"] not in used_cfgs:
            bad.append(f"config {c['name']} is used by no cell")
        want = os.path.relpath(config_file(c["name"]), ROOT)
        if c["file"] != want:
            bad.append(f"config {c['name']} file {c['file']!r} != {want!r}")
        elif os.path.exists(config_file(c["name"])):
            body = _load(config_file(c["name"]))
            if body.get("source") != c["source"]:
                bad.append(f"config {c['name']}: source differs from file")
            if sorted(body.get("reduced", [])) != sorted(c["reduced"]):
                bad.append(f"config {c['name']}: reduced differs from file")
            for k in ("source", "reduced", "assumed", "departures"):
                if k not in body:
                    bad.append(f"config file {c['name']} lacks {k!r}")
        for k in c["reduced"]:
            if k.endswith(("_size", "_dim", "_rank")) or any(
                w in k for w in WIDTH_WORDS
            ):
                bad.append(f"config {c['name']} reduces a width: {k}")
    return bad
