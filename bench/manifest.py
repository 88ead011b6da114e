"""Checks on ``BENCHMARK.json``: the rules a later change to it must keep.

``problems(manifest, root)`` lists every rule the manifest breaks, as
text; an empty list means it holds. The rules: names and units use only
their allowed characters; each metric's ``moves`` target is reported in
each cell of the metric; every cell reports ``setup_s``, another
end-to-end metric and a per-layer metric; every cell's configuration,
traffic, driver and metric files resolve by name.
"""
from __future__ import annotations

import json
import re
from pathlib import Path
from typing import List

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
TOP = ["command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"]
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _line(text) -> bool:
    return isinstance(text, str) and 1 <= len(text) <= 200 \
        and "\n" not in text and "\t" not in text


def _cells(metric: dict, cells: List[str]) -> List[str]:
    return list(metric.get("workloads", cells))


def problems(manifest: dict, root: Path) -> List[str]:
    out: List[str] = []
    if list(manifest) != TOP:
        out.append(f"top-level keys {list(manifest)} != {TOP}")
        return out
    if not (isinstance(manifest["run_seconds"], int)
            and 1 <= manifest["run_seconds"] <= 51):
        out.append("run_seconds must be a whole number from 1 to 51")
    cells = [w["name"] for w in manifest["workloads"]]
    configs = {c["name"]: c for c in manifest["configs"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for section, keys in KEYS.items():
        names = [e.get("name") for e in manifest[section]]
        if len(set(names)) != len(names):
            out.append(f"{section}: duplicate names")
        for e in manifest[section]:
            extra = set(e) - keys - ({"workloads"} if "layer" in keys
                                     or "bound" in keys else set())
            if not keys <= set(e) or extra:
                out.append(f"{section} {e.get('name')}: keys {sorted(e)}")
            if not NAME.fullmatch(str(e.get("name", ""))):
                out.append(f"{section}: bad name {e.get('name')!r}")
            for k in ("why", "layer", "source"):
                if k in e and k in keys and not _line(e[k]):
                    out.append(f"{section} {e['name']}: bad {k}")
            if "unit" in e and not UNIT.fullmatch(e["unit"]):
                out.append(f"{e['name']}: bad unit {e['unit']!r}")
            if "better" in keys and e.get("better") not in ("lower",
                                                            "higher"):
                out.append(f"{e['name']}: better must be lower or higher")
    for c in manifest["configs"]:
        if not (root / c["file"]).is_file():
            out.append(f"config {c['name']}: no file {c['file']}")
        out += [f"config {c['name']}: bad reduced key {k!r}"
                for k in c["reduced"] if not NAME.fullmatch(k)]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if m["source"] not in SOURCES:
            out.append(f"{m['name']}: source {m['source']!r}")
        if not (root / "bench" / "metrics" / f"{m['name']}.py").is_file():
            out.append(f"{m['name']}: no reader bench/metrics/"
                       f"{m['name']}.py")
        out += [f"{m['name']}: unknown cell {w}"
                for w in m.get("workloads", []) if w not in cells]
    for m in manifest["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            out.append(f"{m['name']}: an end-to-end metric is taken by "
                       "the benchmark itself")
        if not 0.01 <= m["bound"] <= 0.25:
            out.append(f"{m['name']}: bound {m['bound']} outside "
                       "[0.01, 0.25]")
    if "setup_s" not in e2e:
        out.append("no setup_s")
    for m in manifest["per_layer"]:
        target = e2e.get(m["moves"])
        if target is None:
            out.append(f"{m['name']}: moves {m['moves']!r}, not an "
                       "end-to-end metric")
            continue
        missing = set(_cells(m, cells)) - set(_cells(target, cells))
        if missing:
            out.append(f"{m['name']}: {m['moves']} is not reported in "
                       f"{sorted(missing)}")
    for w in manifest["workloads"]:
        name = w["name"]
        if w["config"] not in configs:
            out.append(f"{name}: unknown config {w['config']!r}")
        if w["chips"] not in (1, 4):
            out.append(f"{name}: chips must be 1 or 4")
        traffic = root / "bench" / "traffic" / f"{w['traffic']}.json"
        if not traffic.is_file():
            out.append(f"{name}: no traffic file {traffic.name}")
        else:
            with open(traffic) as f:
                driver = json.load(f).get("driver", "")
            if not (root / "bench" / "drivers" / f"{driver}.py").is_file():
                out.append(f"{name}: no driver {driver!r}")
        reported = [m["name"] for m in manifest["end_to_end"]
                    if name in _cells(m, cells)]
        if "setup_s" not in reported or len(reported) < 2:
            out.append(f"{name}: reports {reported}; needs setup_s and "
                       "another end-to-end metric")
        if not any(name in _cells(m, cells) for m in manifest["per_layer"]):
            out.append(f"{name}: no per-layer metric")
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    if len(set(pairs)) != len(pairs):
        out.append("a configuration and traffic pair appears twice")
    return out
