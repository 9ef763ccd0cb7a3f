"""Finding a cell's files by name.

``BENCHMARK.json`` lists the cells; each names a configuration and a
traffic mix.  A cell's pieces live in files of their own under this
directory, found by those names:

* ``configs/<config>.json``: the model, its sizes, level and system;
* ``traffic/<traffic>.json``: the load and the serving knobs;
* ``workloads/<cell>.json``: the limits of the comparison that decides
  ``correct``;
* ``metrics/<metric>.py``: the reader of each per-layer metric;
* ``refs/<reference>.py``: the plain reference a configuration names;
* ``systems/<system>.py``: the code that serves and checks one kind of
  system under test.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import re
from dataclasses import dataclass, field

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parent
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class SpecError(Exception):
    pass


def _name(kind: str, value: str) -> str:
    if not isinstance(value, str) or not NAME.match(value):
        raise SpecError(f"bad {kind} name {value!r}")
    return value


def _json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise SpecError(f"missing {path.relative_to(REPO)}")
    return json.loads(path.read_text())


def load_benchmark(root: pathlib.Path = REPO) -> dict:
    return _json(root / "BENCHMARK.json")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list = field(default_factory=list)
    per_layer: list = field(default_factory=list)
    root: pathlib.Path = HERE

    def metrics(self, trace: bool) -> list:
        return self.per_layer if trace else self.end_to_end


def load_cell(name: str, bench: dict | None = None,
              root: pathlib.Path = HERE) -> Cell:
    bench = load_benchmark(root.parent) if bench is None else bench
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json")
    w = entries[0]
    config = _json(root / "configs" / f"{_name('config', w['config'])}.json")
    traffic = _json(root / "traffic" / f"{_name('traffic', w['traffic'])}.json")
    cell_file = _json(root / "workloads" / f"{_name('workload', name)}.json")
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if m["moves"] in reported and _applies(m, name)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=cell_file["limits"], end_to_end=e2e,
                per_layer=per_layer, root=root)


def load_module(root: pathlib.Path, kind: str, name: str):
    """``<root>/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = root / kind / f"{_name(kind, name)}.py"
    if not path.is_file():
        raise SpecError(f"missing {kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
