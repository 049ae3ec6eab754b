"""Find a cell's pieces by name: ``BENCHMARK.json`` pairs a configuration
file (``configs/<name>.json``) with a traffic file (``traffic/<name>.json``)
and lists the metrics the cell reports."""
from __future__ import annotations

import dataclasses
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent
CHECKOUT = HERE.parent


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


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file: the
    registry entry it names, with every size taken from the file, run as
    a bidirectional denoiser the way ``launch/serve.build_engine`` does."""
    import repro.configs as registry
    from repro.models.config import dense_pattern

    if conf["hidden_act"] != "silu":
        raise ValueError(f"{conf['name']}: only SwiGLU (silu) MLPs are run")
    layers = conf["num_hidden_layers"]
    window = conf.get("sliding_window") or 0
    return registry.get(conf["registry"]).replace(
        n_layers=layers, block_pattern=dense_pattern(layers, window),
        sliding_window=window,
        d_model=conf["hidden_size"], d_ff=conf["intermediate_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        vocab_size=conf["vocab_size"], rope_theta=conf["rope_theta"],
        norm_eps=conf["rms_norm_eps"], mlp_type="swiglu",
        tie_embeddings=conf["tie_word_embeddings"],
        dtype=conf["torch_dtype"], bidirectional=True)
