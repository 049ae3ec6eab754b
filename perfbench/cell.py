"""Find a cell's pieces by name: ``BENCHMARK.json`` pairs a configuration
file (``configs/<name>.json``) with a traffic file (``traffic/<name>.json``)
and lists the metrics the cell reports; the configuration file names the
module that builds it (``models/<model>.py``)."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent
CHECKOUT = HERE.parent
MODELS_DIR = HERE / "models"


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple[dict, ...]    # this cell's end-to-end metrics
    per_layer: tuple[dict, ...]     # this cell's per-layer metrics


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(workload: str, benchmark: pathlib.Path | None = None) -> Cell:
    bench = load_json(benchmark or CHECKOUT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; have "
                       f"{sorted(cells)}")
    w = cells[workload]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    return Cell(
        name=workload, chips=int(w["chips"]),
        config=load_json(CHECKOUT / conf["file"]),
        traffic=load_json(HERE / "traffic" / f"{w['traffic']}.json"),
        end_to_end=tuple(m for m in bench["end_to_end"]
                         if _reports(m, workload)),
        per_layer=tuple(m for m in bench["per_layer"]
                        if _reports(m, workload)))


def model_module(conf: dict):
    """The module that builds a configuration: ``models/<model>.py``,
    named by the file's ``model`` key.  It exposes ``model_config(conf)``,
    the program's ``ModelConfig``, and ``forward_flops(conf, n)``, the
    FLOPs of one forward pass over an ``n``-token canvas."""
    path = MODELS_DIR / f"{conf['model']}.py"
    if not path.is_file():
        raise ValueError(f"{conf['name']}: no model module {path.name} "
                         f"under {MODELS_DIR}")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_model_{conf['model']}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file, as its
    model module builds it."""
    return model_module(conf).model_config(conf)
