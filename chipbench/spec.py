"""Finds every piece of a cell by its name in ``BENCHMARK.json``.

* ``workloads[i]`` names a configuration and a traffic mix;
* a configuration is the ``file`` of its ``configs`` entry, and its
  ``family`` names the plain reference ``models/<family>.py``;
* a traffic mix is ``traffic/<traffic>.json``;
* a per-layer metric is read by ``metrics/<name>.py``, whose ``read(rec)``
  returns the number, or None where the run has nothing to read.

A later cell, configuration, mix or metric is therefore a new file and a
new entry, and no file that is already here changes.  ``root`` is the
checkout that holds ``BENCHMARK.json`` and ``chipbench/``.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
from typing import Callable, List, Optional

ROOT = pathlib.Path(__file__).resolve().parent.parent


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    with open(pathlib.Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def _named(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json "
                   f"(have {[e['name'] for e in entries]})")


def workload(bench: dict, name: str) -> dict:
    return _named(bench["workloads"], name, "workload")


def config(bench: dict, name: str, root: pathlib.Path = ROOT) -> dict:
    entry = _named(bench["configs"], name, "configuration")
    with open(pathlib.Path(root) / entry["file"]) as f:
        return json.load(f)


def traffic(name: str, root: pathlib.Path = ROOT) -> dict:
    with open(pathlib.Path(root) / "chipbench" / "traffic"
              / f"{name}.json") as f:
        return json.load(f)


def _load(path: pathlib.Path, what: str):
    if not path.is_file():
        raise KeyError(f"{what} has no file {path}")
    mod_spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def model_module(family: str, root: pathlib.Path = ROOT):
    """The plain reference ``models/<family>.py``.  It imports no JAX at
    module level, so the parent may read its layer shapes."""
    return _load(pathlib.Path(root) / "chipbench" / "models"
                 / f"{family}.py", f"model family {family!r}")


def metric_reader(name: str, root: pathlib.Path = ROOT
                  ) -> Callable[[dict], Optional[float]]:
    """``metrics/<name>.py``'s ``read`` (names may hold dots, so files
    are loaded by path, not imported by module name)."""
    return _load(pathlib.Path(root) / "chipbench" / "metrics"
                 / f"{name}.py", f"per-layer metric {name!r}").read


def end_to_end_metrics(bench: dict, cell: str) -> List[dict]:
    return [m for m in bench["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]]


def per_layer_metrics(bench: dict, cell: str) -> List[dict]:
    """A per-layer metric with a ``workloads`` key is read in those
    cells; one without it in every cell that reports what it moves."""
    e2e = {m["name"] for m in end_to_end_metrics(bench, cell)}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]
