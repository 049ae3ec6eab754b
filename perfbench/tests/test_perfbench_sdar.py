"""The SDAR configuration in the benchmark: its model module builds the
file's ``ModelConfig`` and refuses what it does not build, its FLOP and
byte counts against hand counts, the three expert readers on a hand-made
trace, and a whole run of a CPU-sized SDAR cell through ``run_cell``
checked against its reference."""
import dataclasses
import types

import pytest

from perfbench import cell, devtrace, flops, peaks, progtrace, run
from perfbench.tests.test_perfbench_faults import tiny_cell

CONF = cell.load_json(cell.HERE / "configs" / "sdar-30b-a3b-ep8.json")
SDAR = cell.model_module(CONF)
MS = 1_000_000.0
READERS = ("moe_gmm_device_ms", "moe_gmm_roofline", "moe_route_device_ms")


def test_builds_the_files_model_config():
    cfg = cell.model_config(CONF)
    assert (cfg.n_experts, cfg.n_held, cfg.expert_offset) == (128, 16, 0)
    assert cfg.experts_per_token == 8 and cfg.d_ff == 768
    assert (cfg.hd, cfg.n_heads, cfg.n_kv_heads) == (128, 32, 4)
    assert cfg.qk_norm and cfg.block_causal == 4
    assert not cfg.time_conditioning and cfg.bidirectional
    assert cfg.block_pattern == ("moe",) * 48
    assert CONF["reduced"] == ["num_experts"]
    assert CONF["num_experts_published"] == 128


@pytest.mark.parametrize("key,value", [
    ("layer_types", ["full_attention"]), ("shared_expert_intermediate_size",
                                          1024),
    ("hidden_act", "gelu"), ("norm_topk_prob", False),
    ("sliding_window", 4096), ("mlp_only_layers", [0]),
    ("decoder_sparse_step", 2), ("attention_bias", True),
    ("tie_word_embeddings", True), ("time_conditioning", True),
    ("expert_offset", 120)])
def test_refuses_what_it_does_not_build(key, value):
    conf = dict(CONF, **{key: value})
    with pytest.raises(ValueError, match=key):
        cell.model_config(conf)
    with pytest.raises(ValueError, match=key):
        flops.denoiser_flops(conf, 256)


def test_forward_flops():
    # per layer at n=256, d=2048, 32 query heads / 4 KV heads of 128:
    #   q,k,v,o    2*256*2048*(4096+2*512) + 2*256*4096*2048 = 9,663,676,416
    #   router     2*256*2048*128                            =   134,217,728
    #   attention  2*2*(256*260/2 visible pairs)*32*128      =   545,259,520
    #   experts    3*2*(256*8*16/128 pairs)*2048*768         = 2,415,919,104
    # 48 layers + head 2*256*2048*151936; no time MLP
    layer = 9_663_676_416 + 134_217_728 + 545_259_520 + 2_415_919_104
    want = 48 * layer + 159_316_443_136
    assert flops.denoiser_flops(CONF, 256) == want == 771_751_936_000
    # one call of 8 rows: 6.17 TFLOP, 31.3 ms at the bf16 peak
    assert 8 * want / 197e12 == pytest.approx(31.34e-3, rel=1e-3)


def test_moe_gmm_work():
    # 8 rows x 256 tokens x top-8 x 16/128 held = 2048 pairs a layer
    f, b = SDAR.moe_gmm_work(CONF, 8, 256)
    assert f == 48 * 3 * 2 * 2048 * 2048 * 768 == 927_712_935_936
    # each held expert's gate, up and down weights once; the fused gate-up
    # reads 2048-wide rows and writes 768-wide ones, down the reverse
    weights = 48 * 3 * 16 * 2048 * 768 * 2
    rows = 48 * 2048 * 2 * (2048 + 768) * 2
    assert b == weights + rows == 8_355_053_568
    t, bound = flops.roofline_seconds(f, b, peaks.for_kind("TPU v5 lite"))
    assert bound == "memory" and t == pytest.approx(10.20e-3, rel=1e-3)


def _trace():
    # window 0..100 ms; the step program runs 10-40 and 50-80 ms; in each
    # a 6 ms grouped matmul inside a 10 ms mlp scope; one more matmul
    # outside any execution is left out
    ops = [("moe_gmm.1", 12 * MS, 15 * MS), ("moe_gmm.2", 15 * MS, 18 * MS),
           ("fusion.3", 18 * MS, 22 * MS),
           ("moe_gmm.1", 52 * MS, 55 * MS), ("moe_gmm.2", 55 * MS, 58 * MS),
           ("fusion.3", 58 * MS, 62 * MS), ("moe_gmm.1", 90 * MS, 95 * MS)]
    modules = [("jit__dndm_rows(7)", 10 * MS, 40 * MS),
               ("jit__dndm_rows(7)", 50 * MS, 80 * MS)]
    tr = devtrace.Trace(ops=ops, modules=modules,
                        host=[("bench.window", 0.0, 100 * MS)],
                        window=(0.0, 100 * MS))
    prog = progtrace.ProgramTrace(
        spans=[], window=(0.0, 100 * MS),
        scoped=[("mlp", s, e) for n, s, e in ops[:6]]
        + [("attention", 22 * MS, 30 * MS)])
    return tr, prog


def _ctx(trace, program):
    return types.SimpleNamespace(
        trace=trace, program=program, devtrace=devtrace, flops=flops,
        conf=CONF, traffic={"canvas": 256, "max_batch": 8},
        peaks=peaks.for_kind("TPU v5 lite"))


def test_readers_on_a_hand_trace():
    ctx = _ctx(*_trace())
    assert run.read_metric("moe_gmm_device_ms", ctx) == pytest.approx(6.0)
    assert run.read_metric("moe_route_device_ms", ctx) == pytest.approx(4.0)
    least = 8_355_053_568 / 819e9
    assert run.read_metric("moe_gmm_roofline", ctx) == pytest.approx(
        100 * least / 6e-3)


@pytest.mark.parametrize("name", READERS)
def test_readers_are_silent_without_the_kernel(name):
    """No trace, or a trace of a program with no grouped matmul (the
    dense cells, or a parent that lacks the kernel): no value, no raise."""
    tr, prog = _trace()
    assert run.read_metric(name, _ctx(None, None)) is None
    dense = devtrace.Trace(ops=[o for o in tr.ops if "moe_gmm" not in o[0]],
                           modules=tr.modules, host=tr.host,
                           window=tr.window)
    assert run.read_metric(name, _ctx(dense, prog)) is None


def tiny_sdar_cell() -> cell.Cell:
    """The SDAR file cut to CPU size (2 layers, d 256, 4 heads over 1 KV
    head at head_dim 128, 8 experts top-2 of which 4 held from offset 4)
    under the tiny backlog mix."""
    c = tiny_cell("backlog")
    conf = dict(CONF, num_hidden_layers=2, hidden_size=256,
                num_attention_heads=4, num_key_value_heads=1,
                moe_intermediate_size=64, num_experts_published=8,
                num_experts_per_tok=2, num_experts=4, expert_offset=4,
                vocab_size=97, mask_id=96, torch_dtype="float32",
                check=c.config["check"])
    return dataclasses.replace(c, config=conf)


def test_sound_sdar_run_is_correct():
    """The whole served path (build, engine, ``ContinuousScheduler``) on a
    CPU-sized SDAR, checked against ``refs/sdar_moe_denoiser.py``: on the
    CPU the program computes in float32 and both gaps read 0."""
    res = run.run_cell(tiny_sdar_cell(), 2**31 + 11, 0.6, False,
                       peaks.for_kind("TPU v5 lite"))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["checks"]["logit_gap"]["value"] == 0.0
    assert res["metrics"]["tokens_per_s"]["value"] > 0
