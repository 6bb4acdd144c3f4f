"""BENCHMARK.json: loading, the contract's checks that can be made
without a run, and finding a cell's files by name."""

from __future__ import annotations

import json
import os
import re
from typing import Any

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter",
           "host_clock")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
# Widths may never be reduced (the contract's list).
_WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj).*size"
                    r"|_dim$|_rank$|head_dim|head_size|expand"
                    r"|experts_per_tok")


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def load(path: str) -> dict[str, Any]:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def _line(text: Any, what: str, problems: list[str]) -> None:
    if not (isinstance(text, str) and 1 <= len(text) <= 200
            and "\n" not in text and "\t" not in text):
        problems.append(f"{what}: 1 to 200 characters on one line")


def problems(manifest: dict[str, Any], root: str) -> list[str]:
    """Every breach of the contract that needs no run; empty = valid."""
    out: list[str] = []
    if set(manifest) != KEYS:
        out.append(f"top-level keys must be exactly {sorted(KEYS)}")
        return out
    paths = manifest["paths"]
    if not (1 <= len(paths) <= 16 and all(
            PATH.match(p) and not p.startswith("/") and ".." not in
            p.split("/") for p in paths)):
        out.append("paths: 1 to 16 relative directories")
    for word in manifest["command"]:
        _line(word, "command word", out)
        if word.startswith("/") or ".." in word.split("/"):
            out.append(f"command word {word!r} leaves the repo")
    if not (isinstance(manifest["run_seconds"], int)
            and 1 <= manifest["run_seconds"] <= 51):
        out.append("run_seconds: a whole number from 1 to 51")

    def under_paths(file: str) -> bool:
        return any(file.startswith(p.rstrip("/") + "/") for p in paths)

    names: dict[str, set] = {k: set() for k in
                             ("configs", "workloads", "metrics")}

    def fresh(kind: str, name: Any) -> None:
        if not (isinstance(name, str) and NAME.match(name)):
            out.append(f"{kind} name {name!r} is not a name")
        elif name in names[kind]:
            out.append(f"{kind} name {name!r} appears twice")
        else:
            names[kind].add(name)

    files = set()
    for c in manifest["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            out.append(f"config {c.get('name')!r}: wrong keys")
            continue
        fresh("configs", c["name"])
        _line(c["source"], f"config {c['name']} source", out)
        _line(c["why"], f"config {c['name']} why", out)
        if not under_paths(c["file"]) or c["file"] in files:
            out.append(f"config {c['name']}: file must lie under paths "
                       "and be its own")
        files.add(c["file"])
        if not os.path.isfile(os.path.join(root, c["file"])):
            out.append(f"config {c['name']}: no file {c['file']}")
        if len(c["reduced"]) > 16:
            out.append(f"config {c['name']}: over 16 reduced keys")
        for key in c["reduced"]:
            if not NAME.match(key) or _WIDTH.search(key):
                out.append(f"config {c['name']}: may not reduce {key!r}")
    pairs = set()
    for w in manifest["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            out.append(f"workload {w.get('name')!r}: wrong keys")
            continue
        fresh("workloads", w["name"])
        _line(w["why"], f"workload {w['name']} why", out)
        if w["config"] not in names["configs"]:
            out.append(f"workload {w['name']}: unknown config")
        if not NAME.match(str(w["traffic"])):
            out.append(f"workload {w['name']}: traffic is not a name")
        if w["chips"] not in (1, 4):
            out.append(f"workload {w['name']}: chips must be 1 or 4")
        if (w["config"], w["traffic"]) in pairs:
            out.append(f"workload {w['name']}: pair appears twice")
        pairs.add((w["config"], w["traffic"]))
    used = {w["config"] for w in manifest["workloads"]
            if isinstance(w, dict) and "config" in w}
    for c in names["configs"] - used:
        out.append(f"config {c!r} is used by no cell")
    four = sum(1 for w in manifest["workloads"] if w.get("chips") == 4)
    if four > max(len(manifest["workloads"]) // 4, 1):
        out.append("over a quarter of the cells ask for four chips")
    if not 1 <= len(manifest["workloads"]) <= 24:
        out.append("workloads: 1 to 24 cells")

    cells = names["workloads"]

    def cells_of(metric: dict) -> set:
        return set(metric.get("workloads", cells))

    e2e: dict[str, set] = {}
    for m in manifest["end_to_end"]:
        if not {"name", "unit", "better", "bound", "source"} <= set(m) \
                or not set(m) <= {"name", "unit", "better", "bound",
                                  "source", "workloads"}:
            out.append(f"end-to-end metric {m.get('name')!r}: wrong keys")
            continue
        fresh("metrics", m["name"])
        e2e[m["name"]] = cells_of(m)
        if m["source"] not in ("host_clock", "device_trace"):
            out.append(f"{m['name']}: end-to-end source must be "
                       "host_clock or device_trace")
        if not (isinstance(m["bound"], (int, float))
                and 0 < m["bound"] <= 0.1):
            out.append(f"{m['name']}: bound must lie in (0, 0.1]")
    if e2e.get("setup_s") != cells:
        out.append("setup_s must be an end-to-end metric of every cell")
    for m in manifest["per_layer"]:
        if not {"name", "unit", "better", "source", "layer",
                "moves"} <= set(m) or not set(m) <= {
                "name", "unit", "better", "source", "layer", "moves",
                "workloads"}:
            out.append(f"per-layer metric {m.get('name')!r}: wrong keys")
            continue
        fresh("metrics", m["name"])
        _line(m["layer"], f"{m['name']} layer", out)
        if m["source"] not in SOURCES:
            out.append(f"{m['name']}: unknown source {m['source']!r}")
        if m["moves"] not in e2e:
            out.append(f"{m['name']}: moves {m['moves']!r}, which is "
                       "no end-to-end metric")
        elif not cells_of(m) <= e2e[m["moves"]]:
            out.append(f"{m['name']}: moves {m['moves']!r}, which some "
                       "of its cells do not report")
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if not UNIT.match(str(m.get("unit", ""))):
            out.append(f"{m.get('name')}: unit {m.get('unit')!r}")
        if m.get("better") not in ("lower", "higher"):
            out.append(f"{m.get('name')}: better must be lower|higher")
        if not cells_of(m) <= cells:
            out.append(f"{m.get('name')}: lists an unknown cell")
    for cell in cells:
        mine = [n for n, cs in e2e.items() if cell in cs]
        if len(mine) < 2:
            out.append(f"cell {cell}: needs setup_s and one more "
                       "end-to-end metric")
        if not any(cell in cells_of(m) for m in manifest["per_layer"]):
            out.append(f"cell {cell}: needs a per-layer metric")
    return out


def cell(manifest: dict[str, Any], name: str) -> dict[str, Any]:
    """The cell called `name`, with its configuration's entry and the
    metrics it reports."""
    for w in manifest["workloads"]:
        if w["name"] == name:
            break
    else:
        known = ", ".join(w["name"] for w in manifest["workloads"])
        raise SystemExit(f"benchmark: no cell {name!r} (have: {known})")
    config = next(c for c in manifest["configs"]
                  if c["name"] == w["config"])

    def mine(metrics: list[dict]) -> list[dict]:
        return [m for m in metrics
                if name in m.get("workloads", [name])]

    return {"workload": w, "config": config,
            "end_to_end": mine(manifest["end_to_end"]),
            "per_layer": mine(manifest["per_layer"])}


def traffic_file(manifest: dict[str, Any], root: str, traffic: str) -> str:
    """`<path>/traffic/<name>.json` under the first of `paths` that has
    it — a later PR's mix lives in a directory of its own."""
    for p in manifest["paths"]:
        candidate = os.path.join(root, p, "traffic", traffic + ".json")
        if os.path.isfile(candidate):
            return candidate
    raise SystemExit(f"benchmark: no traffic file for {traffic!r}")


def kind_file(manifest: dict[str, Any], root: str, kind: str) -> str:
    """`<path>/traffic/kinds/<kind>.py`, likewise: what a session of
    that kind sends and how its sessions are driven."""
    if not NAME.match(str(kind)):
        raise SystemExit(f"benchmark: traffic kind {kind!r} is no name")
    for p in manifest["paths"]:
        candidate = os.path.join(root, p, "traffic", "kinds", kind + ".py")
        if os.path.isfile(candidate):
            return candidate
    raise SystemExit(f"benchmark: no traffic kind {kind!r}")


def reader_file(manifest: dict[str, Any], root: str, metric: str) -> str:
    """`<path>/layer_metrics/<metric>.py`, likewise."""
    for p in manifest["paths"]:
        candidate = os.path.join(root, p, "layer_metrics", metric + ".py")
        if os.path.isfile(candidate):
            return candidate
    raise SystemExit(f"benchmark: no reader for metric {metric!r}")
