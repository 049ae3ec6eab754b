"""A whole benchmark run on the CPU at a tiny size, with the timed path
sound and then broken underneath: each fault the serving cells can have
must turn ``correct`` false.  (The look for a chip is in ``run.main``,
which these tests skip; ``run.run_cell`` is the rest of a run.)"""
import dataclasses

import jax.numpy as jnp
import pytest

from perfbench import cell, peaks, run
from repro.core import decode
from repro.core.samplers import stepwise

PEAKS = peaks.for_kind("TPU v5 lite")
END_TO_END = ({"name": "tokens_per_s", "unit": "tokens/s"},
              {"name": "latency_p50_s", "unit": "s"},
              {"name": "setup_s", "unit": "s"})


def tiny_cell(arrivals="poisson") -> cell.Cell:
    """dndm-text8's file and the text8 open-loop mix, cut to CPU size."""
    conf = cell.load_json(cell.HERE / "configs" / "dndm-text8.json")
    conf.update(num_hidden_layers=2, hidden_size=64, intermediate_size=128,
                num_attention_heads=4, num_key_value_heads=4)
    # on the CPU the program's float32 matmuls are exact float32; at this
    # size both its gaps read 0.0, and the bfloat16 control's widest gap
    # 0.0157 and up and its mean 3.9e-5 and up (test_perfbench_control.py),
    # so each limit lies between them
    conf["check"] = dict(conf["check"], requests=3, block=8,
                         precision="highest", logit_gap_limit=1e-3,
                         mean_logit_gap_limit=1e-5)
    traffic = cell.load_json(cell.HERE / "traffic"
                             / "poisson-t50-b8-r27.json")
    traffic.update(arrivals=arrivals, canvas=16, length_min=8,
                   length_max=16, steps=8, max_batch=4, lead_in_s=0.3,
                   tail_s=5.0, rate_per_s=40.0)
    return cell.Cell(name="tiny", chips=1, config=conf, traffic=traffic,
                     end_to_end=END_TO_END, per_layer=())


def _run(c, seed=3):
    return run.run_cell(c, seed, 0.6, False, PEAKS)


@pytest.mark.parametrize("arrivals", ["poisson", "backlog"])
def test_sound_run_is_correct(arrivals):
    res = _run(tiny_cell(arrivals), seed=2**31 + 5)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    m = res["metrics"]
    assert m["tokens_per_s"]["value"] > 0 and m["setup_s"]["value"] > 0
    if arrivals == "poisson":
        assert m["latency_p50_s"]["value"] > 0
    assert list(res)[-1] == "checks"


def _unchanged(x, tau, t_row, keys, cond, **kw):
    return x


def _half_batch(orig):
    def step(x, tau, t_row, keys, cond, **kw):
        out = orig(x, tau, t_row, keys, cond, **kw)
        rows = jnp.arange(x.shape[0])[:, None] < x.shape[0] // 2
        return jnp.where(rows, out, x)
    return step


def _altered_token(orig):
    def dec(key, logits, noise, cfg, **kw):
        tok, score = orig(key, logits, noise, cfg, **kw)
        bumped = (tok[:, 0] + 1) % (noise.vocab_size - 1)
        return tok.at[:, 0].set(bumped), score
    return dec


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered"])
def test_fault_turns_correct_false(monkeypatch, fault):
    if fault == "state_unchanged":
        monkeypatch.setattr(stepwise, "_dndm_rows", _unchanged)
    elif fault == "half_batch":
        monkeypatch.setattr(stepwise, "_dndm_rows",
                            _half_batch(stepwise._dndm_rows))
    else:
        monkeypatch.setattr(decode, "decode_tokens",
                            _altered_token(decode.decode_tokens))
    res = _run(dataclasses.replace(tiny_cell("backlog")))
    assert not res["correct"], res["checks"]
    failing = [k for k, c in res["checks"].items() if c["value"] > c["limit"]]
    want = ({"logit_gap", "mean_logit_gap"} if fault == "token_altered"
            else {"bad_results"})
    assert want <= set(failing), res["checks"]


def test_a_check_without_a_gap_limit_is_refused():
    c = tiny_cell("backlog")
    spec = {k: v for k, v in c.config["check"].items()
            if not k.endswith("gap_limit")}
    c = dataclasses.replace(c, config=dict(c.config, check=spec))
    with pytest.raises(ValueError, match="limit"):
        _run(c)
