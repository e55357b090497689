"""BENCHMARK.json and the files a cell is made of, found by name.

A cell is data: its entry in `workloads`, its configuration's file, its
traffic file `<dir>/traffic/<traffic>.json`, its limits
`<dir>/limits/<cell>.json`, the driver `<dir>/drivers/<driver>.py` the
traffic names and one reader `<dir>/layer_metrics/<metric>.py` per
per-layer metric. `<dir>` is each directory of `paths`, in order, then
this package's own directory; so a PR adds a cell by adding files and one
entry, and edits nothing that is there.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class Manifest:
    def __init__(self, root: str) -> None:
        self.root = os.path.abspath(root)
        with open(os.path.join(self.root, "BENCHMARK.json")) as f:
            self.data: Dict[str, Any] = json.load(f)
        self.dirs = [os.path.join(self.root, p) for p in self.data["paths"]]
        if HERE not in self.dirs:
            self.dirs.append(HERE)

    def find(self, *parts: str) -> str:
        for d in self.dirs:
            path = os.path.join(d, *parts)
            if os.path.exists(path):
                return path
        raise FileNotFoundError(
            f"{os.path.join(*parts)} is in none of {self.dirs}")

    def _entry(self, group: str, name: str) -> Dict[str, Any]:
        for e in self.data[group]:
            if e["name"] == name:
                return e
        raise KeyError(f"no {name!r} in {group} "
                       f"({[e['name'] for e in self.data[group]]})")

    def cell(self, name: str) -> Dict[str, Any]:
        """The cell's entry with its configuration, traffic and limits."""
        cell = dict(self._entry("workloads", name))
        cfg_entry = self._entry("configs", cell["config"])
        with open(os.path.join(self.root, cfg_entry["file"])) as f:
            cell["config_data"] = json.load(f)
        with open(self.find("traffic", cell["traffic"] + ".json")) as f:
            cell["traffic_data"] = json.load(f)
        with open(self.find("limits", name + ".json")) as f:
            cell["limits"] = json.load(f)
        return cell

    def metrics(self, group: str, cell: str) -> List[Dict[str, Any]]:
        """The metrics of `end_to_end` or `per_layer` this cell reports."""
        return [m for m in self.data[group]
                if "workloads" not in m or cell in m["workloads"]]

    def driver(self, name: str):
        return _load(self.find("drivers", name + ".py"), f"driver_{name}")

    def layer_metric(self, name: str):
        return _load(self.find("layer_metrics", name + ".py"),
                     "layer_metric_" + re.sub(r"\W", "_", name))

    def peaks(self) -> Dict[str, Any]:
        with open(self.find("peaks.json")) as f:
            return json.load(f)


def _load(path: str, modname: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def problems(m: Manifest) -> List[str]:
    """What the contract would refuse, as far as the files can show."""
    d, out = m.data, []
    want = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(d) != want:
        out.append(f"keys {sorted(set(d) ^ want)}")
    names: Dict[str, str] = {}

    def name_ok(n: str, what: str, unique: Optional[str] = None) -> None:
        if not NAME.match(n):
            out.append(f"{what}: bad name {n!r}")
        if unique:
            if names.setdefault(f"{unique}:{n}", what) != what:
                out.append(f"{what}: {n!r} twice")

    for i, c in enumerate(d["configs"]):
        name_ok(c["name"], f"configs[{i}]", "config")
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            out.append(f"configs[{i}]: keys {sorted(c)}")
        if not os.path.isfile(os.path.join(m.root, c["file"])):
            out.append(f"configs[{i}]: no file {c['file']}")
        if not any(os.path.abspath(os.path.join(m.root, c["file"]))
                   .startswith(os.path.join(m.root, p) + os.sep)
                   for p in d["paths"]):
            out.append(f"configs[{i}]: {c['file']} is outside paths")
    used, four = set(), 0
    for i, w in enumerate(d["workloads"]):
        name_ok(w["name"], f"workloads[{i}]", "cell")
        name_ok(w["traffic"], f"workloads[{i}].traffic")
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            out.append(f"workloads[{i}]: keys {sorted(w)}")
        if w["chips"] not in (1, 4):
            out.append(f"workloads[{i}]: chips {w['chips']}")
        four += w["chips"] == 4
        if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"]:
            out.append(f"workloads[{i}]: why of {len(w['why'])} characters")
        used.add(w["config"])
        try:
            cell = m.cell(w["name"])
            m.driver(cell["traffic_data"]["driver"])
        except (KeyError, OSError) as e:
            out.append(f"workloads[{i}]: {e}")
    if four > max(1, len(d["workloads"]) // 4):
        out.append(f"{four} four-chip cells of {len(d['workloads'])}")
    if used != {c["name"] for c in d["configs"]}:
        out.append("a configuration no cell uses, or a cell without one")
    cells = {w["name"] for w in d["workloads"]}
    e2e = {e["name"] for e in d["end_to_end"]}
    for group in ("end_to_end", "per_layer"):
        for i, e in enumerate(d[group]):
            what = f"{group}[{i}]"
            name_ok(e["name"], what, "metric")
            if not UNIT.match(e["unit"]):
                out.append(f"{what}: bad unit {e['unit']!r}")
            if e["better"] not in ("lower", "higher"):
                out.append(f"{what}: better {e['better']!r}")
            if e["source"] not in SOURCES:
                out.append(f"{what}: source {e['source']!r}")
            if not set(e.get("workloads", ())) <= cells:
                out.append(f"{what}: unknown cell in workloads")
            if group == "end_to_end":
                if e["source"] not in ("host_clock", "device_trace"):
                    out.append(f"{what}: source {e['source']!r}")
                if not 0 < e["bound"] <= 0.1:
                    out.append(f"{what}: bound {e['bound']}")
            else:
                if e["moves"] not in e2e:
                    out.append(f"{what}: moves {e['moves']!r}")
                try:
                    m.find("layer_metrics", e["name"] + ".py")
                except FileNotFoundError as err:
                    out.append(f"{what}: {err}")
    for e in d["per_layer"]:
        for c in e.get("workloads", cells):
            if e["moves"] not in {x["name"]
                                  for x in m.metrics("end_to_end", c)}:
                out.append(f"per_layer {e['name']}: cell {c} does not "
                           f"report {e['moves']}")
    if "setup_s" not in e2e:
        out.append("no setup_s")
    for c in cells:
        if len(m.metrics("end_to_end", c)) < 2 or not m.metrics(
                "per_layer", c):
            out.append(f"cell {c}: too few metrics")
    if not 1 <= d["run_seconds"] <= 51:
        out.append(f"run_seconds {d['run_seconds']}")
    return out
