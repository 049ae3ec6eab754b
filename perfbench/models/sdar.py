"""SDAR (the Qwen3-MoE decoder layer) as a denoiser on one chip's share of
an expert-parallel deployment: how a configuration file that names
``"model": "sdar"`` is built, what one forward pass of it costs, and what
one step's grouped matmuls move.

The layer: pre-norm RMSNorm; q/k/v projections for ``num_attention_heads``
query and ``num_key_value_heads`` KV heads at ``head_dim``; a per-head
RMSNorm on q and k, then RoPE; softmax attention under the block-causal
mask (bidirectional inside a block of ``block_length`` positions, causal
across blocks); o and the residual add; RMSNorm, a float32 softmax router
over all ``num_experts_published`` experts, top-``num_experts_per_tok``
with the gates renormalised, and SwiGLU experts of width
``moe_intermediate_size``, of which this chip holds ``num_experts`` from
``expert_offset``.  No time input; an untied LM head.

A file that states something this module does not build (a dense or
windowed layer, shared experts, unnormalised gates, another activation,
biases, RoPE scaling, tied embeddings, a time input) is refused with the
key named.
"""
from __future__ import annotations

from perfbench.flops import DTYPE_BYTES

# key: (what the module builds, what the key would ask for otherwise)
_BUILT = {
    "hidden_act": ("silu", "another expert activation"),
    "attention_bias": (False, "biases on the attention projections"),
    "decoder_sparse_step": (1, "dense layers between the sparse ones"),
    "mlp_only_layers": ([], "dense layers between the sparse ones"),
    "norm_topk_prob": (True, "gates that are not renormalised"),
    "rope_scaling": (None, "scaled RoPE"),
    "sliding_window": (None, "windowed attention"),
    "use_sliding_window": (False, "windowed attention"),
    "tie_word_embeddings": (False, "a head tied to the embedding"),
    "time_conditioning": (False, "a diffusion-time input"),
}
_NOT_BUILT = {
    "layer_types": "per-layer attention kinds",
    "shared_expert_intermediate_size": "a shared expert",
    "n_shared_experts": "a shared expert",
    "num_local_experts": "experts counted under another key",
    "n_routed_experts": "experts counted under another key",
}


def check(conf: dict) -> None:
    """Raise ``ValueError`` naming the first key of ``conf`` that this
    module does not build."""
    name = conf.get("name", "?")
    for key, what in _NOT_BUILT.items():
        if conf.get(key):
            raise ValueError(f"{name}: {key}={conf[key]!r} asks for {what}; "
                             "the sdar model does not build it")
    for key, (built, what) in _BUILT.items():
        if key in conf and conf[key] != built:
            raise ValueError(f"{name}: {key}={conf[key]!r} asks for {what}; "
                             f"the sdar model builds only {built!r}")
    held, offset = conf["num_experts"], conf["expert_offset"]
    if not 0 <= offset <= conf["num_experts_published"] - held:
        raise ValueError(f"{name}: expert_offset={offset} with "
                         f"num_experts={held} held lies outside the "
                         f"{conf['num_experts_published']} experts")


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file: the
    registry entry the file names, with every size taken from the file."""
    import repro.configs as registry
    from repro.models.config import moe_pattern

    check(conf)
    layers = conf["num_hidden_layers"]
    return registry.get(conf["registry"]).replace(
        n_layers=layers, block_pattern=moe_pattern(layers),
        d_model=conf["hidden_size"], d_ff=conf["moe_intermediate_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        vocab_size=conf["vocab_size"], rope_theta=float(conf["rope_theta"]),
        norm_eps=conf["rms_norm_eps"], mlp_type="swiglu",
        n_experts=conf["num_experts_published"],
        experts_per_token=conf["num_experts_per_tok"],
        held_experts=conf["num_experts"], expert_offset=conf["expert_offset"],
        qk_norm=True, block_causal=conf["block_length"],
        time_conditioning=False, tie_embeddings=False,
        dtype=conf["torch_dtype"], bidirectional=True)


def held_pairs(conf: dict, tokens: float) -> float:
    """Expected token-expert pairs routed to the held experts among
    ``tokens`` tokens, under uniform routing."""
    return (tokens * conf["num_experts_per_tok"] * conf["num_experts"]
            / conf["num_experts_published"])


def forward_flops(conf: dict, n: int) -> float:
    """FLOPs of one forward pass over one canvas of ``n`` tokens (one row
    of one batched call): per layer the q/k/v/o projections at
    ``head_dim``, the router, attention scores and values over the
    n(n+b)/2 query-key pairs the block-causal mask leaves visible (n a
    multiple of the block b), and the held experts' SwiGLU at the
    expected pairs routed here; then the LM head.  There is no time MLP.
    Norms, RoPE, softmax and the gating are elementwise and left out."""
    check(conf)
    d, h, kv = (conf["hidden_size"], conf["num_attention_heads"],
                conf["num_key_value_heads"])
    hd, b = conf["head_dim"], conf["block_length"]
    proj = 2 * n * d * (h * hd + 2 * kv * hd) + 2 * n * h * hd * d
    router = 2 * n * d * conf["num_experts_published"]
    attn = 2 * 2 * (n * (n + b) // 2) * h * hd
    experts = 3 * 2 * held_pairs(conf, n) * d * conf["moe_intermediate_size"]
    head = 2 * n * d * conf["vocab_size"]
    return float(conf["num_hidden_layers"] * (proj + router + attn + experts)
                 + head)


def moe_gmm_work(conf: dict, rows: int, n: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one step's grouped matmuls over ``rows`` canvases
    of ``n`` tokens, all layers: per layer the gate, up and down matmuls
    at the expected held pairs; the bytes are every held expert's three
    weight matrices, read once, and the routed rows in and out (the fused
    gate-up SwiGLU reads the d-wide rows once and writes f-wide ones, down
    reads those and writes d-wide ones)."""
    check(conf)
    d, f = conf["hidden_size"], conf["moe_intermediate_size"]
    size = DTYPE_BYTES[conf["torch_dtype"]]
    pairs = held_pairs(conf, rows * n)
    flops = 3 * 2 * pairs * d * f
    weights = 3 * conf["num_experts"] * d * f * size
    acts = pairs * 2 * (d + f) * size
    layers = conf["num_hidden_layers"]
    return float(layers * flops), float(layers * (weights + acts))
