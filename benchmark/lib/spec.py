"""What ``BENCHMARK.json`` and the data files beside the harness say.

A cell is one ``workloads`` entry of ``BENCHMARK.json``: a configuration
(``configs/<config>.json``) under a traffic mix (``traffic/<traffic>.json``,
which names the driver that plays it).  A per-layer metric is one
``per_layer`` entry plus ``metrics/<name>.json`` (its reader and the
reader's arguments).  ``limits/<cell>.json`` holds the limit of each
number the cell's check compares.  Drivers and readers are modules found by name in
``drivers/`` and ``readers/``.  Adding a cell, a configuration, a
driver or a metric is adding files and entries: no file here is edited.
"""
import importlib.util
import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class SpecError(ValueError):
    pass


def check_name(kind, name):
    if not isinstance(name, str) or not NAME.match(name):
        raise SpecError("%s name %r: letters, digits, '_', '.', '-' only, "
                        "at most 64" % (kind, name))
    return name


def check_unit(metric, unit):
    if not isinstance(unit, str) or not UNIT.match(unit):
        raise SpecError("metric %s: unit %r not allowed" % (metric, unit))
    return unit


def _load_json(path):
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError("missing file %s" % path) from None


class Spec:
    """``root`` holds BENCHMARK.json; ``bench_dir`` (default
    ``<root>/benchmark``) holds configs/, traffic/, metrics/, drivers/,
    readers/."""

    def __init__(self, root, bench_dir=None):
        self.root = os.path.abspath(root)
        self.bench_dir = os.path.abspath(
            bench_dir or os.path.join(self.root, "benchmark"))
        self.doc = _load_json(os.path.join(self.root, "BENCHMARK.json"))
        for group in ("configs", "workloads", "end_to_end", "per_layer"):
            seen = set()
            for entry in self.doc[group]:
                name = check_name(group, entry["name"])
                if name in seen:
                    raise SpecError("%s: %r twice" % (group, name))
                seen.add(name)
        for m in self.doc["end_to_end"] + self.doc["per_layer"]:
            check_unit(m["name"], m["unit"])
            if m["better"] not in ("lower", "higher"):
                raise SpecError("metric %s: better=%r" % (m["name"],
                                                          m["better"]))
        self.cells = {w["name"]: w for w in self.doc["workloads"]}
        self.configs = {c["name"]: c for c in self.doc["configs"]}
        e2e = {m["name"] for m in self.doc["end_to_end"]}
        for w in self.cells.values():
            check_name("config", w["config"])
            check_name("traffic", w["traffic"])
            if w["config"] not in self.configs:
                raise SpecError("cell %s: unknown config %r"
                                % (w["name"], w["config"]))
            if w["chips"] not in (1, 4):
                raise SpecError("cell %s: chips %r" % (w["name"],
                                                       w["chips"]))
        for m in self.doc["end_to_end"] + self.doc["per_layer"]:
            for c in m.get("workloads", ()):
                if c not in self.cells:
                    raise SpecError("metric %s lists unknown cell %r"
                                    % (m["name"], c))
        for m in self.doc["per_layer"]:
            if m["moves"] not in e2e:
                raise SpecError("metric %s moves unknown %r"
                                % (m["name"], m["moves"]))

    # -- files found by name ------------------------------------------
    def path(self, *parts):
        return os.path.join(self.bench_dir, *parts)

    def cell(self, name):
        try:
            return self.cells[name]
        except KeyError:
            raise SpecError("unknown workload %r (known: %s)" % (
                name, ", ".join(sorted(self.cells)))) from None

    def config(self, cell):
        entry = self.configs[cell["config"]]
        doc = _load_json(os.path.join(self.root, entry["file"]))
        try:
            doc["sizes"] = {ours: int(doc[theirs])
                            for ours, theirs in doc["keys"].items()}
        except KeyError as e:
            raise SpecError("%s: 'keys' names %s, which the file lacks"
                            % (entry["file"], e)) from None
        return doc

    def limits(self, cell):
        doc = _load_json(self.path("limits", cell["name"] + ".json"))
        return {k: float(v) for k, v in doc["limits"].items()}

    def traffic(self, cell):
        doc = _load_json(self.path("traffic", cell["traffic"] + ".json"))
        check_name("driver", doc.get("driver"))
        return doc

    def metric_file(self, name):
        doc = _load_json(self.path("metrics", name + ".json"))
        check_name("reader", doc.get("reader"))
        if not isinstance(doc.get("args", {}), dict):
            raise SpecError("metric %s: args must be an object" % name)
        return doc

    def _module(self, kind, name):
        path = self.path(kind, check_name(kind, name) + ".py")
        if not os.path.exists(path):
            raise SpecError("no %s %r (%s)" % (kind[:-1], name, path))
        mod_spec = importlib.util.spec_from_file_location(
            "benchmark_%s_%s" % (kind, name.replace("-", "_").replace(
                ".", "_")), path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return mod

    def driver(self, name):
        return self._module("drivers", name)

    def reader(self, name):
        return self._module("readers", name)

    # -- which metrics a cell reports ---------------------------------
    def end_to_end(self, cell):
        return [m for m in self.doc["end_to_end"]
                if "workloads" not in m or cell["name"] in m["workloads"]]

    def per_layer(self, cell):
        mine = {m["name"] for m in self.end_to_end(cell)}
        out = []
        for m in self.doc["per_layer"]:
            if "workloads" in m:
                if cell["name"] in m["workloads"]:
                    out.append(m)
            elif m["moves"] in mine:
                out.append(m)
        return out
