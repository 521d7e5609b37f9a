"""Finds a cell's configuration, traffic mix and metric readers by name.

``BENCHMARK.json`` at the checkout root names each cell's configuration
and traffic mix; the configuration entry names its file.  A traffic mix
``<mix>`` is ``traffic/<mix>.json`` and a metric ``<name>`` is read by
``metrics/<name>.py`` (a function ``read(rec)``).  A configuration names
its architecture by ``reference``: the plain reference
``references/<reference>.py`` and the program adapter
``programs/<reference>.py``.  Adding a cell, a mix, a metric or an
architecture is adding files and entries; no existing file changes.

A metric with a ``workloads`` list is reported in those cells.  An
end-to-end metric without one is reported in every cell; a per-layer
metric without one in every cell that reports the end-to-end metric it
``moves``, so a new cell picks up every per-layer metric of what it
reports without an edit to an existing entry.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    here: Path
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def _reported(metric: dict, cell: str, e2e: set[str] | None = None) -> bool:
    """Whether ``cell`` reports ``metric``; ``e2e`` is the set of its
    end-to-end metrics when ``metric`` is a per-layer one."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e is None or metric["moves"] in e2e


def load(cell: str, root: Path = ROOT, here: Path = HERE) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    by_name = {w["name"]: w for w in bench["workloads"]}
    if cell not in by_name:
        raise KeyError(f"no workload {cell!r} in BENCHMARK.json; "
                       f"known: {sorted(by_name)}")
    w = by_name[cell]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads((here / "traffic" / f"{w['traffic']}.json").read_text())
    end_to_end = [m for m in bench["end_to_end"] if _reported(m, cell)]
    e2e = {m["name"] for m in end_to_end}
    per_layer = [m for m in bench["per_layer"] if _reported(m, cell, e2e)]
    return Cell(
        name=cell, chips=int(w["chips"]), here=here, config=config,
        traffic=traffic, end_to_end=end_to_end, per_layer=per_layer,
    )


@functools.cache
def _module(here: Path, kind: str, name: str):
    """``<here>/<kind>/<name>.py``, loaded once."""
    path = here / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(
            f"no {kind} module {name!r}: add {path.relative_to(here.parents[1])}")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.chip.{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str, here: Path = HERE):
    """The ``read`` function of ``metrics/<metric>.py``."""
    return _module(here, "metrics", metric).read


def reference(config: dict, here: Path = HERE):
    """The plain reference module the configuration names."""
    return _module(here, "references", config["reference"])


def adapter(config: dict, here: Path = HERE):
    """The program adapter of the configuration's reference: its
    ``arch_config(config)`` gives the program's ``ArchConfig``."""
    return _module(here, "programs", config["reference"])
