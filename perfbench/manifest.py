"""BENCHMARK.json and the files its names resolve to.

The harness is driven by data: a configuration, a traffic mix, a cell and a
per-layer metric are each a file found by the name ``BENCHMARK.json`` gives,
so a later PR adds files and entries and edits nothing that is there.
``load`` checks every name against the contract and that every one resolves,
before anything touches JAX.
"""

from __future__ import annotations

import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
HERE = os.path.dirname(os.path.abspath(__file__))


class ManifestError(ValueError):
    pass


def _need(cond, what):
    if not cond:
        raise ManifestError(what)


def _read(path):
    with open(path) as fh:
        return json.load(fh)


def _line(s, what):
    _need(isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s
          and "\t" not in s, f"{what}: 1 to 200 characters on one line")


class Manifest:
    def __init__(self, root: str, bench_dir: str = HERE):
        self.root = root
        self.dir = bench_dir
        self.doc = _read(os.path.join(root, "BENCHMARK.json"))
        self._check()

    # ------------------------------------------------------------ files
    def file(self, kind: str, name: str) -> str:
        return os.path.join(self.dir, kind, name + ".json")

    def config(self, name: str) -> dict:
        entry = next(c for c in self.doc["configs"] if c["name"] == name)
        return _read(os.path.join(self.root, entry["file"]))

    def traffic(self, name: str) -> dict:
        return _read(self.file("traffic", name))

    def cell(self, name: str) -> dict:
        """The cell's own file merged over its BENCHMARK.json entry."""
        entry = next((w for w in self.doc["workloads"] if w["name"] == name),
                     None)
        _need(entry is not None,
              f"no workload {name!r} in BENCHMARK.json; it has "
              f"{[w['name'] for w in self.doc['workloads']]}")
        return {**entry, **_read(self.file("workloads", name))}

    def layer_metric(self, name: str) -> dict:
        return _read(self.file("layer_metrics", name))

    def metrics_of(self, group: str, cell: str) -> list:
        """Entries of ``end_to_end`` or ``per_layer`` that exist in ``cell``."""
        return [m for m in self.doc[group]
                if "workloads" not in m or cell in m["workloads"]]

    # ------------------------------------------------------------ checks
    def _check(self):
        d = self.doc
        _need(set(d) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"},
              "BENCHMARK.json: exactly the contract's seven keys")
        _need(isinstance(d["run_seconds"], int) and 1 <= d["run_seconds"] <= 51,
              "run_seconds: a whole number from 1 to 51")
        for word in d["command"]:
            _line(word, "command word")
        names = set()
        for c in d["configs"]:
            _need(set(c) == {"name", "source", "file", "reduced", "why"},
                  f"config entry keys: {sorted(c)}")
            self._name(c["name"], names, "config")
            _line(c["source"], "config source")
            _line(c["why"], "config why")
            _need(any(c["file"].startswith(p.rstrip("/") + "/")
                      for p in d["paths"]), f"{c['file']} lies outside paths")
            _need(os.path.exists(os.path.join(self.root, c["file"])),
                  f"config file {c['file']} does not exist")
            _need(len(c["reduced"]) <= 16 and all(NAME.match(k)
                                                  for k in c["reduced"]),
                  f"config {c['name']}: reduced keys")
            body = self.config(c["name"])
            _need(sorted(body.get("reduced", [])) == sorted(c["reduced"]),
                  f"config {c['name']}: its file and its entry list "
                  "different reduced keys")
        configs = {c["name"] for c in d["configs"]}
        cells, pairs = set(), set()
        four = sum(1 for w in d["workloads"] if w.get("chips") == 4)
        _need(four <= max(1, len(d["workloads"]) // 4),
              "at most a quarter of the cells, and always one, may take 4 chips")
        for w in d["workloads"]:
            _need(set(w) == {"name", "config", "traffic", "chips", "why"},
                  f"workload entry keys: {sorted(w)}")
            self._name(w["name"], cells, "workload")
            _need(NAME.match(w["traffic"]), f"traffic name {w['traffic']!r}")
            _need(w["config"] in configs, f"{w['name']}: unknown config")
            _need(w["chips"] in (1, 4), f"{w['name']}: chips is 1 or 4")
            _line(w["why"], f"{w['name']} why")
            _need((w["config"], w["traffic"]) not in pairs,
                  f"{w['name']}: its config and traffic pair appears twice")
            pairs.add((w["config"], w["traffic"]))
            for kind, name in (("traffic", w["traffic"]),
                               ("workloads", w["name"])):
                _need(os.path.exists(self.file(kind, name)),
                      f"{w['name']}: no file {kind}/{name}.json")
            own = _read(self.file("workloads", w["name"]))
            for key in ("config", "traffic", "chips"):
                _need(own.get(key, w[key]) == w[key],
                      f"{w['name']}: its file and its entry differ on {key}")
        _need(configs == {w["config"] for w in d["workloads"]},
              "every configuration is used by some cell")
        metrics = set()
        for group in ("end_to_end", "per_layer"):
            for m in d[group]:
                keys = {"name", "unit", "better", "source"}
                keys |= {"bound"} if group == "end_to_end" else {"layer", "moves"}
                _need(set(m) - {"workloads"} == keys,
                      f"metric entry keys: {sorted(m)}")
                self._name(m["name"], metrics, "metric")
                _need(UNIT.match(m["unit"]), f"{m['name']}: unit {m['unit']!r}")
                _need(m["better"] in ("lower", "higher"), f"{m['name']}: better")
                _need(m["source"] in SOURCES, f"{m['name']}: source")
                _need(set(m.get("workloads", cells)) <= cells,
                      f"{m['name']}: lists a cell that does not exist")
        for m in d["end_to_end"]:
            _need(m["source"] in ("host_clock", "device_trace"),
                  f"{m['name']}: an end-to-end metric is taken by the benchmark")
            _need(0.01 <= m["bound"] <= 0.1, f"{m['name']}: bound in [0.01, 0.1]")
        e2e = {m["name"] for m in d["end_to_end"]}
        _need("setup_s" in e2e, "setup_s is an end-to-end metric")
        for m in d["per_layer"]:
            _need(m["moves"] in e2e, f"{m['name']}: moves no end-to-end metric")
            _line(m["layer"], f"{m['name']} layer")
            _need(os.path.exists(self.file("layer_metrics", m["name"])),
                  f"no file layer_metrics/{m['name']}.json")
            own = self.layer_metric(m["name"])
            for key in ("unit", "layer", "moves"):
                _need(own.get(key) == m[key],
                      f"{m['name']}: its file and its entry differ on {key}")

    @staticmethod
    def _name(name, seen, what):
        _need(isinstance(name, str) and NAME.match(name), f"{what} name {name!r}")
        _need(name not in seen, f"{what} name {name!r} appears twice")
        seen.add(name)


def load(root: str, bench_dir: str = HERE) -> Manifest:
    return Manifest(root, bench_dir)
