"""Finds everything a cell needs by name, and assembles its result line.

For a cell ``<name>`` of ``BENCHMARK.json`` with ``config`` C and
``traffic`` T:

* ``bench/configs/C.json``   the configuration as it is run (sizes, arch
  id in ``repro.configs``, source, ``reduced``, ``assumed``, reference);
* ``bench/references/<reference>.py``   its plain reference, which also
  gives the architecture's parameter tree, how each leaf is drawn, and
  the operations and bytes of its steps;
* ``bench/traffic/T.json``   the traffic's parameters; its ``entry`` names
  the driver ``bench/entries/<entry>.py`` that generates that traffic
  against one entry point of the program;
* ``bench/limits/<name>.json``   the limits of the numbers compared for
  ``correct``; a cell without one has no limits set, so it runs, prints
  its numbers and comes out not correct (how its limits are first read);
* ``bench/metrics/<metric>.py``   one reader per per-layer metric.

Nothing here names a cell, a configuration or a metric.
"""

from __future__ import annotations

import importlib.util
import json
import math
import sys
import typing
from dataclasses import dataclass, fields, is_dataclass, replace
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class BenchError(RuntimeError):
    """The benchmark cannot produce a sound result line."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict         # bench/configs/<config>.json
    traffic: dict        # bench/traffic/<traffic>.json
    limits: dict         # bench/limits/<cell>.json
    end_to_end: list     # metric entries of BENCHMARK.json for this cell
    per_layer: list
    bench_dir: Path = BENCH_DIR   # where its files were found


def _json(path: Path) -> dict:
    if not path.is_file():
        raise BenchError(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def load_module(path: Path, name: str):
    if not path.is_file():
        raise BenchError(f"missing {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell_name: str, reported: set | None) -> bool:
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    return reported is None or metric.get("moves") in reported


def load_cell(name: str, bench_dir: Path = BENCH_DIR,
              benchmark: dict | None = None) -> Cell:
    root = bench_dir.parent
    spec = benchmark or _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    e2e = [m for m in spec["end_to_end"] if _applies(m, name, None)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"] if _applies(m, name, reported)]
    limits = bench_dir / "limits" / f"{name}.json"
    return Cell(
        name=name, chips=w["chips"],
        config=_json(bench_dir / "configs" / f"{w['config']}.json"),
        traffic=_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
        limits=_json(limits) if limits.is_file() else {},
        end_to_end=e2e, per_layer=per_layer, bench_dir=bench_dir)


def entry_module(cell: Cell):
    entry = cell.traffic["entry"]
    return load_module(cell.bench_dir / "entries" / f"{entry}.py",
                       f"bench_entry_{entry}")


def reference_module(cell: Cell):
    ref = cell.config["reference"]
    return load_module(cell.bench_dir / "references" / f"{ref}.py",
                       f"bench_reference_{ref}")


def _options(cls, given: dict, where: str) -> None:
    unknown = set(given) - {f.name for f in fields(cls)}
    if unknown:
        raise BenchError(f"{where} keys the program has no option for: "
                         f"{sorted(unknown)}")


def _group(key: str, given: dict, base, hint):
    """A nested dataclass field built from its dict: over the preset's
    value where it has one, else from the field's own type."""
    cls = type(base) if is_dataclass(base) else next(
        (t for t in (hint, *typing.get_args(hint)) if is_dataclass(t)), None)
    if cls is None:
        raise BenchError(f"model.{key} is a group, and the program's "
                         f"option is not")
    _options(cls, given, f"model.{key}")
    try:
        return replace(base, **given) if is_dataclass(base) else cls(**given)
    except TypeError as e:
        raise BenchError(f"model.{key}: {e}") from None


def model_config(config: dict):
    """The program's ModelConfig for this configuration: the preset of
    ``arch_id`` with every size of the file's ``model`` applied; a group
    of sizes (a dict) becomes the option's own dataclass."""
    from repro.configs import get_config

    base = get_config(config["arch_id"])
    model = dict(config["model"])
    _options(type(base), model, "model")
    hints = typing.get_type_hints(type(base))
    for k, v in model.items():
        if isinstance(v, dict):
            model[k] = _group(k, v, getattr(base, k), hints[k])
    return replace(base, **model)


def peaks(device_kind: str, bench_dir: Path = BENCH_DIR) -> dict:
    table = _json(bench_dir / "peaks.json")["devices"]
    if device_kind not in table:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json")
    return table[device_kind]


def read_per_layer(cell: Cell, rec: dict) -> dict:
    """Each per-layer metric from its own reader; a reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for m in cell.per_layer:
        mod = load_module(cell.bench_dir / "metrics" / f"{m['name']}.py",
                          f"bench_metric_{m['name'].replace('.', '_')}")
        v = mod.read(rec)
        if v is None:
            continue
        v = float(v)
        if not math.isfinite(v):
            raise BenchError(f"{m['name']} read {v}")
        if m["unit"] == "%" and v > 100.0:
            raise BenchError(
                f"{m['name']} read {v}% of a peak: the operations or bytes "
                f"are counted too high, or the time leaves out work")
        out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def judge(checks: list[dict]) -> bool:
    """``correct``: every number compared lies at or under its limit; a
    limit that is not set (null) fails."""
    return bool(checks) and all(
        c["value"] is not None and c["limit"] is not None
        and math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks)
