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
    """Each file against the ``ModelConfig`` its own model module builds,
    and that against the registry entry the file names."""
    conf = cell.load_json(cell.HERE / "configs" / f"{name}.json")
    reg = registry.get(conf["registry"])
    run = cell.model_config(conf)
    assert conf["name"] == name
    assert (cell.MODELS_DIR / f"{conf['model']}.py").exists()
    assert conf["hidden_size"] == run.d_model == reg.d_model
    assert run.d_ff == reg.d_ff
    assert conf["num_attention_heads"] == run.n_heads == reg.n_heads
    assert conf["num_key_value_heads"] == run.n_kv_heads == reg.n_kv_heads
    assert conf["vocab_size"] == run.vocab_size == reg.vocab_size
    assert conf["num_hidden_layers"] == run.n_layers == reg.n_layers
    assert conf["mask_id"] == conf["vocab_size"] - 1
    for key in ("source", "reduced", "assumed", "deployment", "check"):
        assert key in conf
    assert run.bidirectional and run.dtype == conf["torch_dtype"]


def _dense_before_models(conf):
    """``cell.model_config`` as it was before configurations named their
    model module: every file was built as the dense denoiser."""
    from repro.models.config import dense_pattern
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


@pytest.mark.parametrize("name", ["dndm-text8", "phi3-mini-3.8b"])
def test_dense_files_build_as_before(name):
    """The dense files build the same ``ModelConfig`` as before the model
    modules, and ``flops.denoiser_flops`` is their module's count (whose
    values ``test_perfbench_flops`` pins)."""
    conf = cell.load_json(cell.HERE / "configs" / f"{name}.json")
    assert conf["model"] == "dense"
    assert cell.model_config(conf) == _dense_before_models(conf)
    dense = cell.model_module(conf)
    assert flops.denoiser_flops(conf, 256) == dense.forward_flops(conf, 256)


@pytest.mark.parametrize("key,value", [
    ("num_experts", 128), ("num_local_experts", 8), ("layer_types",
                                                    ["full_attention"]),
    ("head_dim", 128), ("hidden_act", "gelu"), ("time_conditioning", False)])
def test_dense_refuses_what_it_does_not_build(key, value):
    conf = dict(cell.load_json(cell.HERE / "configs" / "dndm-text8.json"),
                **{key: value})
    with pytest.raises(ValueError, match=key):
        cell.model_config(conf)
    with pytest.raises(ValueError, match=key):
        flops.denoiser_flops(conf, 256)


def test_dense_takes_a_head_dim_equal_to_width_over_heads():
    conf = dict(cell.load_json(cell.HERE / "configs" / "dndm-text8.json"),
                head_dim=64, num_experts=0, time_conditioning=True)
    assert cell.model_config(conf).hd == 64


def test_model_module_found_by_name(tmp_path, monkeypatch):
    """A configuration whose ``model`` names a module put in the models
    directory is built and counted by that module, with no edit to the
    harness: a later model comes as new files only."""
    (tmp_path / "toy.py").write_text(
        "def model_config(conf):\n"
        "    return ('toy', conf['hidden_size'])\n\n\n"
        "def forward_flops(conf, n):\n"
        "    return 3 * n * conf['hidden_size']\n")
    monkeypatch.setattr(cell, "MODELS_DIR", tmp_path)
    conf = {"name": "toy-config", "model": "toy", "hidden_size": 5}
    assert cell.model_config(conf) == ("toy", 5)
    assert flops.denoiser_flops(conf, 7) == 105.0
    with pytest.raises(ValueError, match="absent"):
        cell.model_config(dict(conf, model="absent"))


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
