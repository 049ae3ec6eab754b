"""Operations and bytes of the served work, counted from shapes.

Counts are the algorithm's, not the compiled program's: a matmul of
(m, k) by (k, n) is 2mkn FLOPs, padding added by a kernel wrapper is not
work, and nothing recomputed counts twice.
"""
from __future__ import annotations

from perfbench import cell

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}


def denoiser_flops(conf: dict, n: int) -> float:
    """FLOPs of one forward pass of the denoiser over one canvas of ``n``
    tokens, as the configuration's model module counts them."""
    return float(cell.model_module(conf).forward_flops(conf, n))


def decode_scores_work(batch: int, n: int, vocab: int, logits_dtype: str,
                       sample: bool) -> tuple[float, float]:
    """(FLOPs, bytes) of one streaming (token, score) decode over a
    (batch, n, vocab) logit tensor: it reads the logits once, the f32
    Gumbel slab once in sample mode and the (vocab,) mask, and writes an
    int32 token and an f32 score per position.  Per logit it adds the mask
    (and the noise), keeps a running max and argmax, and folds one
    exponential into the running logsumexp: about 8 operations."""
    elems = batch * n * vocab
    per_elem = 8 + (1 if sample else 0)
    bytes_ = (elems * DTYPE_BYTES[logits_dtype] + (elems * 4 if sample else 0)
              + vocab * 4 + batch * n * 8)
    return float(elems * per_elem), float(bytes_)


def roofline_seconds(flops: float, bytes_: float, peaks: dict) -> tuple[
        float, str]:
    """The least time the chip could take and which bound sets it."""
    t_flops = flops / peaks["bf16_flops"]
    t_bytes = bytes_ / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes,
                                                            "memory")
