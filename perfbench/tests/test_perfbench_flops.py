"""FLOP and byte counts against hand counts for both configurations."""
import pytest

from perfbench import cell, flops, peaks


def _conf(name):
    return cell.load_json(cell.HERE / "configs" / f"{name}.json")


def test_text8_forward_flops():
    # per layer at n=256, d=768, 12 heads of 64, d_ff 3072:
    #   q,k,v,o   4 * 2*256*768*768          = 1,207,959,552
    #   attention 2 * 2*256*256*768          =   201,326,592
    #   SwiGLU    3 * 2*256*768*3072         = 3,623,878,656
    # 12 layers + head 2*256*768*28 + time MLP 2*2*768*768
    layer = 1_207_959_552 + 201_326_592 + 3_623_878_656
    want = 12 * layer + 11_010_048 + 2_359_296
    assert flops.denoiser_flops(_conf("dndm-text8"), 256) == want
    assert want == pytest.approx(60.41e9, rel=1e-3)


def test_phi3_forward_flops():
    # d=3072, 32 heads of 96 (kv 32), d_ff 8192, vocab 32064, n=256
    d, f, n, v = 3072, 8192, 256, 32064
    layer = 4 * 2 * n * d * d + 2 * 2 * n * n * d + 3 * 2 * n * d * f
    want = 32 * layer + 2 * n * d * v + 2 * 2 * d * d
    got = flops.denoiser_flops(_conf("phi3-mini-3.8b"), n)
    assert got == want
    # one batched call of 8 rows: 15.5 TFLOP, MLP about two thirds
    assert 8 * got == pytest.approx(15.45e12, rel=1e-2)
    assert 32 * 3 * 2 * n * d * f / got == pytest.approx(0.64, abs=0.01)


def test_decode_scores_work_phi3():
    # bf16 logits + f32 Gumbel slab at (8, 256, 32064), mask, two outputs
    f, b = flops.decode_scores_work(8, 256, 32064, "bfloat16", True)
    elems = 8 * 256 * 32064
    assert b == elems * 2 + elems * 4 + 32064 * 4 + 8 * 256 * 8
    assert f == elems * 9
    t, bound = flops.roofline_seconds(f, b, peaks.for_kind("TPU v5 lite"))
    assert bound == "memory"
    assert t == pytest.approx(b / 819e9)
    assert t == pytest.approx(0.48e-3, rel=0.01)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.for_kind("TPU v9 imaginary")
