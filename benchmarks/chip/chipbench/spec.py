"""Finds a cell's data by name: ``BENCHMARK.json`` at the checkout's root,
``configs/<config>.json`` (via the configuration's ``file``),
``traffic/<traffic>.json`` and ``metrics/<metric>.py`` (or, for a metric
split by cell kind, ``metrics/<reader>.py``).

A configuration, a traffic mix or a metric is added by adding its file and
its ``BENCHMARK.json`` entry; nothing here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Any, Callable, Optional

#: ``benchmarks/chip``: the directory that holds the benchmark
BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
#: the checkout's root, where ``BENCHMARK.json`` lives
CHECKOUT = BENCH_DIR.parents[1]


class SpecError(ValueError):
    """A cell, configuration, traffic mix or metric that cannot be found or
    read."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict          # the configuration file's contents
    traffic_name: str
    traffic: dict         # the traffic file's contents
    end_to_end: list      # BENCHMARK.json metric entries reported with --trace 0
    per_layer: list       # ... and with --trace 1


def _read_json(path: pathlib.Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None
    except json.JSONDecodeError as e:
        raise SpecError(f"{path}: {e}") from None


def metrics_for(bench: dict, cell: str) -> tuple[list, list]:
    """The end-to-end and per-layer metric entries a cell reports. An
    end-to-end metric is reported in the cells its ``workloads`` lists, or
    in every cell where it has none; a per-layer metric in the cells its
    ``workloads`` lists, which it must have."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    for m in bench["per_layer"]:
        if "workloads" not in m:
            raise SpecError(f"per-layer metric {m['name']!r} lists no "
                            "workloads")
    return e2e, [m for m in bench["per_layer"] if cell in m["workloads"]]


def load_cell(name: str, checkout: pathlib.Path = CHECKOUT,
              bench_dir: pathlib.Path = BENCH_DIR) -> Cell:
    bench = _read_json(checkout / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no cell {name!r} in BENCHMARK.json; cells: "
                        f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"cell {name!r} names unknown config {w['config']!r}")
    config = _read_json(checkout / configs[w["config"]]["file"])
    traffic = _read_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    e2e, per_layer = metrics_for(bench, name)
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic_name=w["traffic"], traffic=traffic,
                end_to_end=e2e, per_layer=per_layer)


def load_reader(metric: str, bench_dir: pathlib.Path = BENCH_DIR
                ) -> Callable[[Any], Optional[float]]:
    """``metrics/<metric>.py``'s ``read(ctx)``: the metric's value, or None
    where the run holds nothing to read it from. A metric split by the
    end-to-end metric its cells report, ``<reader>.<suffix>`` (for one,
    ``step_mfu.chat``), is read by ``metrics/<reader>.py`` where it has no
    file of its own."""
    path = bench_dir / "metrics" / f"{metric}.py"
    if not path.is_file():
        path = bench_dir / "metrics" / f"{metric.split('.')[0]}.py"
    if not path.is_file():
        raise SpecError(f"no reader {path} for metric {metric!r}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{path.stem.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"{path} defines no read(ctx)")
    return mod.read
