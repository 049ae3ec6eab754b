"""Configuration files (every one under ``configs/``) against the
repository's registry, and the benchmark's cells against the files they
name."""
import re

import pytest

import repro.configs as registry
from perfbench import cell, flops

BENCH = cell.load_json(cell.CHECKOUT / "BENCHMARK.json")
CONFIGS = sorted(f.stem for f in (cell.HERE / "configs").glob("*.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("name", CONFIGS)
def test_config_matches_registry_widths(name):
    conf = cell.load_json(cell.HERE / "configs" / f"{name}.json")
    reg = registry.get(conf["registry"])
    assert conf["name"] == name
    assert conf["hidden_size"] == reg.d_model
    assert conf["intermediate_size"] == reg.d_ff
    assert conf["num_attention_heads"] == reg.n_heads
    assert conf["num_key_value_heads"] == reg.n_kv_heads
    assert conf["vocab_size"] == reg.vocab_size
    assert conf["num_hidden_layers"] == reg.n_layers
    assert conf["mask_id"] == conf["vocab_size"] - 1
    for key in ("source", "reduced", "assumed", "deployment", "check"):
        assert key in conf
    run = cell.model_config(conf)
    assert run.bidirectional and run.dtype == conf["torch_dtype"]
    assert run.n_layers == conf["num_hidden_layers"]


def test_phi3_states_published_dtype():
    conf = cell.load_json(cell.HERE / "configs" / "phi3-mini-3.8b.json")
    assert conf["torch_dtype"] == "bfloat16"
    assert conf["reduced"] == []


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_files_exist(w):
    c = cell.load(w["name"])
    assert c.chips == 1
    assert c.config["name"] == w["config"]
    names = {m["name"] for m in c.per_layer}
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and names
    for m in c.per_layer:
        assert m["moves"] in e2e, (m["name"], m["moves"])
        assert (cell.HERE / "metrics" / f"{m['name']}.py").exists()
    assert flops.denoiser_flops(c.config, c.traffic["canvas"]) > 0


def test_names_and_units():
    things = (BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"]
              + BENCH["per_layer"])
    for t in things:
        assert NAME.match(t["name"]), t["name"]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert len({t["name"] for t in things}) == len(things)
