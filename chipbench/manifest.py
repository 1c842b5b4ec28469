"""BENCHMARK.json and the data files it names: loaded, checked against the
contract's character sets, and resolved to files by name. Nothing here
imports jax, so the tests and the entry point can read the manifest before
any device is touched.

A cell names a configuration and a traffic mix; a per-layer metric names a
reader. Each is one file, found by its name alone:

    chipbench/configs/<config>.json         (the manifest gives the path)
    chipbench/traffic/<traffic>.json
    chipbench/layer_metrics/<metric>.json   -> chipbench/readers/<reader>.py
    chipbench/limits/<workload>.json
"""

import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "chipbench")

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    pass


def check_name(name, what="name"):
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise ManifestError("%s %r: 1-64 of letters, digits, '_', '.', '-'"
                            % (what, name))
    return name


def check_unit(unit):
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise ManifestError("unit %r: 1-16 of letters, digits, '_/%%.-'"
                            % (unit,))
    return unit


def _line(text, what):
    if not isinstance(text, str) or not 1 <= len(text) <= 200 \
            or "\n" in text or "\t" in text:
        raise ManifestError("%s must be 1-200 characters on one line" % what)


def _load_json(path):
    with open(path) as f:
        return json.load(f)


class Manifest(object):
    """The checked contents of BENCHMARK.json, with look-ups by name."""

    def __init__(self, root=ROOT):
        self.root = root
        self.data = _load_json(os.path.join(root, "BENCHMARK.json"))
        d = self.data
        self.configs = {c["name"]: c for c in d["configs"]}
        self.cells = {w["name"]: w for w in d["workloads"]}
        self.end_to_end = {m["name"]: m for m in d["end_to_end"]}
        self.per_layer = {m["name"]: m for m in d["per_layer"]}
        self._check()

    def _check(self):
        d = self.data
        for group, key in (("configs", "configs"), ("workloads", "cells"),
                           ("end_to_end", "end_to_end"),
                           ("per_layer", "per_layer")):
            names = [check_name(e["name"], group) for e in d[group]]
            if len(set(names)) != len(names):
                raise ManifestError("duplicate name in %s" % group)
        if set(self.end_to_end) & set(self.per_layer):
            raise ManifestError("a metric is both end to end and per layer")
        for c in d["configs"]:
            _line(c["source"], "source of %s" % c["name"])
            _line(c["why"], "why of %s" % c["name"])
            for k in c["reduced"]:
                check_name(k, "reduced key")
            self._under_paths(c["file"])
        pairs = set()
        for w in d["workloads"]:
            check_name(w["config"], "config")
            check_name(w["traffic"], "traffic")
            _line(w["why"], "why of %s" % w["name"])
            if w["config"] not in self.configs:
                raise ManifestError("cell %s: unknown config %s"
                                    % (w["name"], w["config"]))
            if w["chips"] not in (1, 4):
                raise ManifestError("cell %s: chips is 1 or 4" % w["name"])
            if (w["config"], w["traffic"]) in pairs:
                raise ManifestError("config/traffic pair twice: %s"
                                    % w["name"])
            pairs.add((w["config"], w["traffic"]))
        for m in d["end_to_end"] + d["per_layer"]:
            check_unit(m["unit"])
            if m["better"] not in ("lower", "higher"):
                raise ManifestError("%s: better is lower or higher"
                                    % m["name"])
            if m["source"] not in SOURCES:
                raise ManifestError("%s: unknown source %r"
                                    % (m["name"], m["source"]))
            for w in m.get("workloads", ()):
                if w not in self.cells:
                    raise ManifestError("%s lists unknown cell %s"
                                        % (m["name"], w))
        if "setup_s" not in self.end_to_end:
            raise ManifestError("setup_s must be an end-to-end metric")
        for m in d["end_to_end"]:
            if not 0 < m["bound"] <= 0.1:
                raise ManifestError("%s: bound in (0, 0.1]" % m["name"])
        for m in d["per_layer"]:
            _line(m["layer"], "layer of %s" % m["name"])
            if m["moves"] not in self.end_to_end:
                raise ManifestError("%s moves unknown metric %s"
                                    % (m["name"], m["moves"]))

    def _under_paths(self, rel):
        norm = os.path.normpath(rel)
        if not any(norm == p or norm.startswith(p.rstrip("/") + "/")
                   for p in self.data["paths"]):
            raise ManifestError("%s is not under paths" % rel)
        return os.path.join(self.root, norm)

    # ---- look-ups ----

    def cell(self, name):
        try:
            return self.cells[name]
        except KeyError:
            raise ManifestError("no workload %r in BENCHMARK.json (has: %s)"
                                % (name, ", ".join(sorted(self.cells))))

    def config_of(self, cell):
        entry = self.configs[cell["config"]]
        return _load_json(self._under_paths(entry["file"]))

    def traffic_of(self, cell):
        return load_traffic(cell["traffic"], self.root)

    def metrics_of(self, cell, group):
        """Metrics of `group` ('end_to_end' | 'per_layer') that this cell
        reports: those that list it, and those that list no cells."""
        out = []
        for m in self.data[group]:
            cells = m.get("workloads")
            if cells is None or cell["name"] in cells:
                out.append(m)
        return out


def load_traffic(name, root=ROOT):
    check_name(name, "traffic")
    return _load_json(os.path.join(root, "chipbench", "traffic",
                                   name + ".json"))


def load_limits(workload, root=ROOT):
    """The limits of what `correct` compares in one cell, the control's
    operand treatment, and the readings they were set from."""
    check_name(workload, "workload")
    return _load_json(os.path.join(root, "chipbench", "limits",
                                   workload + ".json"))


def load_layer_metric(name, root=ROOT):
    """The data file of one per-layer metric: {"reader", "args", ...}."""
    check_name(name, "metric")
    return _load_json(os.path.join(root, "chipbench", "layer_metrics",
                                   name + ".json"))
