"""The dense Transformer denoiser: how a configuration file that names
``"model": "dense"`` is built and what one forward pass of it costs.

The program runs the file as ``launch/serve.build_engine`` does: the
registry entry the file names, with every size taken from the file, as a
bidirectional denoiser with a SwiGLU MLP and the diffusion-time MLP.  A
file that states something this module does not build (sparse experts,
per-layer kinds, a head size decoupled from the width, another MLP
activation, no time conditioning) is refused with the key named, so that
no file runs as a dense model under another model's name.
"""
from __future__ import annotations

# keys a dense file may not state, and what each would ask for
_NOT_BUILT = {
    "num_experts": "sparse expert layers",
    "num_local_experts": "sparse expert layers",
    "n_routed_experts": "sparse expert layers",
    "layer_types": "per-layer attention kinds",
}


def check(conf: dict) -> None:
    """Raise ``ValueError`` naming the first key of ``conf`` that the
    dense denoiser does not build."""
    name = conf.get("name", "?")
    for key, what in _NOT_BUILT.items():
        if conf.get(key):
            raise ValueError(f"{name}: {key}={conf[key]!r} asks for {what}; "
                             "the dense model does not build them")
    width = conf["hidden_size"] // conf["num_attention_heads"]
    if conf.get("head_dim", width) != width:
        raise ValueError(f"{name}: head_dim={conf['head_dim']} differs from "
                         f"hidden_size / num_attention_heads = {width}; the "
                         "dense model ties the head size to the width")
    if conf["hidden_act"] != "silu":
        raise ValueError(f"{name}: hidden_act={conf['hidden_act']!r}; the "
                         "dense model runs only SwiGLU (silu) MLPs")
    if conf.get("time_conditioning", True) is not True:
        raise ValueError(f"{name}: time_conditioning="
                         f"{conf['time_conditioning']!r}; the dense denoiser "
                         "always adds the diffusion-time MLP")


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    import repro.configs as registry
    from repro.models.config import dense_pattern

    check(conf)
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


def forward_flops(conf: dict, n: int) -> float:
    """FLOPs of one forward pass of the denoiser over one canvas of ``n``
    tokens (one row of one batched network call): per layer the q/k/v/o
    projections, bidirectional attention scores and values over all
    ``n`` keys, and the SwiGLU MLP; then the LM head and the time-embedding
    MLP.  Norms, RoPE and softmax are elementwise and left out."""
    check(conf)
    d = conf["hidden_size"]
    f = conf["intermediate_size"]
    h = conf["num_attention_heads"]
    kv = conf["num_key_value_heads"]
    hd = d // h
    v = conf["vocab_size"]
    window = conf.get("sliding_window") or n
    keys = min(window, n)
    proj = 2 * n * d * (h * hd + 2 * kv * hd) + 2 * n * h * hd * d
    attn = 2 * 2 * n * keys * h * hd
    mlp = 3 * 2 * n * d * f
    layer = proj + attn + mlp
    head = 2 * n * d * v
    time_mlp = 2 * 2 * d * d
    return float(conf["num_hidden_layers"] * layer + head + time_mlp)
