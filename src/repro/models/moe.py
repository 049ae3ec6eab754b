"""Mixture-of-Experts MLP: dropless on one device, capacity-routed in
the dry-run's sharded dispatches.

Top-k routing (Mixtral: 8e top-2; Llama-4-Maverick: 128e top-1; SDAR /
Qwen3-MoE: 128e top-8).  The router is a float32 softmax over all
``n_experts``; the k gates are renormalised to sum to one.

An expert layer may hold only a share of the experts (``held_experts``
of them, from ``expert_offset``): one chip's part of an expert-parallel
deployment.  The router keeps its full width, and the layer returns the
part of the result its own experts give, the chip's partial sum; what
the absent experts add is left out.

The single-device path (``_dispatch_ffn``, which serving and training
use) drops nothing: the token-expert pairs routed to a held expert are
sorted by expert and run through grouped matmuls (``kernels/moe_gmm``:
the SwiGLU's gate and up in one, then down) whose work follows the
pairs, so a token's output never depends on the other tokens of its
batch.  Given the layer's place in the stacked weights of a layer scan
(``experts``), the kernel reads the weights there rather than from a
per-layer copy.  The buffer has T*K rows, enough for any routing, but
only its first ``sum(sizes)`` rows, the held pairs', are read.  A layer
that holds a share of the experts (``n_held < n_experts``) fills and
reads only those rows, on a TPU, in ``kernels/moe_route``'s
``moe_dispatch`` and ``moe_combine``; a layer that holds every expert,
where every row is live, keeps XLA's gathers over the whole buffer.
Named scopes ``moe_route`` (router, top-k, sort, dispatch, combine) and
``moe_experts`` (the grouped matmuls) split it inside the block's
``mlp`` scope.

The dry-run's ``local`` and ``shard_map`` dispatches still route into
per-expert buffers of capacity
``C = ceil(top_k * tokens / E * capacity_factor)`` via an argsort on
expert id and drop the pairs past it (their expert contribution is zero;
the residual path still carries them).  Their expert FFNs run as a
single batched einsum over stacked weights (E, d, ff): with
expert-parallel sharding on the model axis this is the all-to-all
pattern the roofline's collective term tracks.

Auxiliary losses: router z-loss and load-balance loss (returned, weighted
by the trainer).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import moe_route as route
from repro.kernels.moe_gmm import gmm
from repro.models.config import ModelConfig
from repro.models.layers import dense_init

Array = jnp.ndarray


def init(key, cfg: ModelConfig) -> dict:
    """The router over all ``n_experts`` and the held experts' weights."""
    E, d, ff = cfg.n_held, cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.dtype)
    std = 1.0 / d ** 0.5
    p = {
        "router": dense_init(ks[0], d, cfg.n_experts, dt, scale=0.1),
        "down": (jax.random.truncated_normal(ks[3], -2, 2, (E, ff, d)) *
                 (1.0 / ff ** 0.5)).astype(dt),
    }
    if cfg.mlp_type == "swiglu":
        p["gate"] = (jax.random.truncated_normal(ks[1], -2, 2, (E, d, ff)) *
                     std).astype(dt)
    p["up"] = (jax.random.truncated_normal(ks[2], -2, 2, (E, d, ff)) *
               std).astype(dt)
    return p


def capacity(cfg: ModelConfig, n_tokens: int) -> int:
    c = int(cfg.experts_per_token * n_tokens / cfg.n_experts
            * cfg.capacity_factor)
    return max(8, -(-c // 8) * 8)        # round up to 8 for tiling


def apply(params: dict, x: Array, cfg: ModelConfig,
          experts: tuple | None = None) -> tuple[Array, dict]:
    """x: (B, S, d) -> (y, aux_losses).

    ``experts``, optional, is ``(stack, layer)``: this block's expert
    weights stacked over the layer scan, of which ``params`` holds slice
    ``layer``; the dropless path's kernel reads them in the stack.

    ``cfg.moe_dispatch == "local"`` splits the tokens into
    ``moe_local_groups`` groups (aligned with the data-parallel shards)
    and dispatches *within* each group: every op is batched over the
    sharded leading group dim, so GSPMD never has to reason across
    shards through the sort — the §Perf fix for the collective-bound
    MoE baselines.  With ample capacity both dispatches compute the
    same token-expert assignments.
    """
    B, S, d = x.shape
    T = B * S
    G = cfg.moe_local_groups
    if cfg.n_held != cfg.n_experts and cfg.moe_dispatch != "global":
        raise ValueError(
            f"held_experts={cfg.held_experts} needs moe_dispatch 'global': "
            f"the '{cfg.moe_dispatch}' dispatch routes into buffers of all "
            f"{cfg.n_experts} experts")
    if cfg.moe_dispatch == "shard_map":
        y, aux = _apply_shard_map(params, x, cfg)
        if y is not None:
            return y, aux
        # no ambient mesh (unit tests / single device): fall through
    if cfg.moe_dispatch == "local" and G > 1 and T % G == 0:
        xg = x.reshape(G, T // G, d)
        C = capacity(cfg, T // G)
        y, aux = jax.vmap(lambda xt: _capacity_ffn(params, xt, cfg, C))(xg)
        return (y.reshape(B, S, d),
                jax.tree.map(lambda a: a.mean(0), aux))
    y, aux = _dispatch_ffn(params, x.reshape(T, d), cfg, experts)
    return y.reshape(B, S, d), aux


def _apply_shard_map(params: dict, x: Array, cfg: ModelConfig):
    """§Perf: shard_map MoE — the GSPMD-proof dispatch.

    The sort-based dispatch defeats GSPMD's sharding propagation (it
    replicates the expert buffers across the data axis and all-gathers
    the tokens).  Inside shard_map every op is *local by construction*:
    tokens stay on their data shard, dispatch/sort run per shard, expert
    FFNs run on the local (E, d, ff/m) tensor-parallel weight shards, and
    the only collective is one explicit psum over the model axis for the
    ff contraction.  Requires an ambient mesh (``jax.set_mesh``);
    returns (None, None) when there is none so callers can fall back.
    """
    am = jax.sharding.get_abstract_mesh()
    if am is None or not am.axis_names or "model" not in am.axis_names:
        return None, None
    from jax.sharding import PartitionSpec as P
    axes = am.axis_names
    dax = tuple(a for a in axes if a != "model")
    B, S, d = x.shape
    n_data = 1
    for a in dax:
        n_data *= am.shape[a]
    if B % n_data or cfg.d_ff % am.shape["model"]:
        return None, None
    T_loc = (B // n_data) * S
    C = capacity(cfg, T_loc)
    m = am.shape["model"]
    # expert-parallel when experts divide the model axis (llama4: 128/16)
    # — tokens travel to their experts via all-to-all; otherwise
    # tensor-parallel expert weights with one psum on the ff contraction.
    ep = bool(cfg.n_experts % m == 0 and cfg.n_experts >= m)

    if ep:
        w_specs = {"router": P(), "up": P("model", None, None),
                   "down": P("model", None, None)}
        if cfg.mlp_type == "swiglu":
            w_specs["gate"] = P("model", None, None)
    else:
        w_specs = {"router": P(), "up": P(None, None, "model"),
                   "down": P(None, "model", None)}
        if cfg.mlp_type == "swiglu":
            w_specs["gate"] = P(None, None, "model")
    in_specs = ({k: w_specs[k] for k in params},
                P(dax if len(dax) > 1 else dax[0], None, None))
    out_specs = (P(dax if len(dax) > 1 else dax[0], None, None),
                 {"load_balance": P(), "router_z": P(),
                  "dropped_frac": P()})

    def local_fn(p, xl):
        Bl, Sl, dl = xl.shape
        xt = xl.reshape(Bl * Sl, dl)
        if ep:
            # activations are replicated over "model" (TP elsewhere), so
            # each model-rank takes its 1/m token slice, dispatches via
            # all-to-all, and an all-gather rebuilds the full activation
            mi = jax.lax.axis_index("model")
            Tm = xt.shape[0] // m
            xt_m = jax.lax.dynamic_slice_in_dim(xt, mi * Tm, Tm)
            y_m, aux = _dispatch_ffn_ep(p, xt_m, cfg,
                                        capacity(cfg, Tm), "model")
            y = jax.lax.all_gather(y_m, "model", axis=0, tiled=True)
        else:
            y, aux = _capacity_ffn(p, xt, cfg, C)
            y = jax.lax.psum(y, "model")      # ff-contraction partials
        aux = jax.tree.map(lambda a: jax.lax.pmean(a, dax), aux)
        return y.reshape(Bl, Sl, dl), aux

    return jax.shard_map(local_fn, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(params, x)


def _router(params: dict, xt: Array, cfg: ModelConfig):
    """(T, d) -> router logits and probabilities (T, E) in float32, and
    the top-k gates, renormalised, with their expert ids (T, K)."""
    logits = jnp.dot(xt, params["router"],
                     preferred_element_type=jnp.float32)     # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, cfg.experts_per_token)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)
    return logits, probs, gate_vals, expert_idx


def _aux(logits, probs, flat_e, cfg: ModelConfig, kept) -> dict:
    E = cfg.n_experts
    fe = jnp.bincount(flat_e, length=E) / flat_e.shape[0]
    return {"load_balance": (E * jnp.sum(probs.mean(0) * fe)
                             ).astype(jnp.float32),
            "router_z": jnp.mean(
                jax.nn.logsumexp(logits, -1) ** 2).astype(jnp.float32),
            "dropped_frac": 1.0 - kept}


def _dispatch_ffn(params: dict, xt: Array, cfg: ModelConfig,
                  experts: tuple | None = None) -> tuple[Array, dict]:
    """Dropless route + held experts + combine on (T, d) tokens: the
    pairs routed to a held expert, sorted by expert, run through grouped
    matmuls over a buffer of T*K rows (enough for any routing); each
    token then sums its held pairs' outputs times their gates in float32.
    Buffer rows past the held pairs are never read.

    Which rows move: a layer that holds every expert fills and reads the
    whole buffer with XLA's gathers.  A layer that holds a share
    (``n_held < n_experts``) moves only the held pairs' rows, on a TPU,
    with the ``kernels/moe_route`` kernels: the dispatch writes the first
    ``sum(sizes)`` rows and the combine reads them back.  The kernels hold
    the tokens in VMEM as float32, so they take at most 48 MiB / (8 d)
    tokens in bfloat16 (3,072 at d 2048, 12 rows of 256 positions); a
    larger batch falls back to XLA's gathers.  ``held_share`` in the
    returned aux is the share of the T*K pairs that are held."""
    T, d = xt.shape
    K, H = cfg.experts_per_token, cfg.n_held
    share = cfg.n_held < cfg.n_experts
    moved = share and route.use_kernel(xt)
    with jax.named_scope("moe_route"):
        logits, probs, gate_vals, expert_idx = _router(params, xt, cfg)
        flat_e = expert_idx.reshape(-1)                      # (T*K,)
        local = flat_e - cfg.expert_offset
        held = (local >= 0) & (local < H)
        local = jnp.where(held, local, H)
        order = jnp.argsort(local, stable=True)              # held first
        sizes = (local[:, None] == jnp.arange(H)).sum(0, dtype=jnp.int32)
        if moved:
            count = sizes.sum()                              # held pairs
            h = route.dispatch(xt, order, count, K)
        else:
            h = route.dispatch_ref(xt, order, K)             # (T*K, d)

    def stacked(first, second=None):
        if experts is None:
            return None
        stack, layer = experts
        return (stack[first], stack[second] if second else None,
                layer)
    with jax.named_scope("moe_experts"):
        if cfg.mlp_type == "swiglu":
            a = gmm(h, params["gate"], sizes, params["up"],
                    stacked=stacked("gate", "up"))
        else:
            a = jax.nn.gelu(gmm(h, params["up"], sizes,
                                stacked=stacked("up")))
        out = gmm(a, params["down"], sizes, stacked=stacked("down"))
    with jax.named_scope("moe_route"):
        if moved:
            y = route.combine(out, gate_vals, order, held, count,
                              xt.dtype)
        else:
            y = route.combine_ref(out, route.slots(order), held,
                                  gate_vals, xt.dtype)
    aux = _aux(logits, probs, flat_e, cfg, 1.0)
    aux["held_share"] = (held.mean(dtype=jnp.float32) if share
                         else jnp.float32(1.0))
    return y, aux


def _route(params: dict, xt: Array, cfg: ModelConfig, C: int):
    """Sort-based capacity routing: tokens -> (E, C, d) expert buffers.

    Returns (buffers h, combine-state dict, aux losses)."""
    T, d = xt.shape
    E, K = cfg.n_experts, cfg.experts_per_token
    logits, probs, gate_vals, expert_idx = _router(params, xt, cfg)

    # flatten the K assignments, sort by expert id (stable => FIFO rank)
    flat_e = expert_idx.reshape(-1)                          # (T*K,)
    flat_tok = jnp.repeat(jnp.arange(T), K)
    flat_gate = gate_vals.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sg = flat_e[order], flat_tok[order], flat_gate[order]
    # rank within expert = position - first position of that expert
    pos = jnp.arange(T * K)
    counts = jnp.bincount(se, length=E)
    starts = jnp.cumsum(counts) - counts
    rank = pos - starts[se]
    keep = rank < C
    slot = jnp.where(keep, se * C + rank, E * C)             # E*C = trash

    buf = jnp.zeros((E * C + 1, d), xt.dtype).at[slot].set(xt[st])
    h = buf[: E * C].reshape(E, C, d)
    aux = _aux(logits, probs, flat_e, cfg, keep.mean())
    state = {"st": st, "sg": sg, "keep": keep, "slot": slot, "T": T}
    return h, state, aux


def _expert_ffn(params: dict, h: Array, cfg: ModelConfig) -> Array:
    """(E, C, d) -> (E, C, d) through the per-expert (Sw)iGLU FFN."""
    if cfg.mlp_type == "swiglu":
        a = jax.nn.silu(jnp.einsum("ecd,edf->ecf", h, params["gate"]))
        h = a * jnp.einsum("ecd,edf->ecf", h, params["up"])
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", h, params["up"]))
    return jnp.einsum("ecf,efd->ecd", h, params["down"])


def _combine(out: Array, state: dict, dtype) -> Array:
    """(E, C, d) expert outputs -> (T, d) gated token outputs."""
    EC, d = out.shape[0] * out.shape[1], out.shape[2]
    out = out.reshape(EC, d)
    keep, slot, st, sg = (state["keep"], state["slot"], state["st"],
                          state["sg"])
    gathered = jnp.where(keep[:, None], out[jnp.minimum(slot, EC - 1)], 0.0)
    return jnp.zeros((state["T"], d), dtype).at[st].add(
        gathered * sg[:, None].astype(dtype))


def _capacity_ffn(params: dict, xt: Array, cfg: ModelConfig,
                  C: int) -> tuple[Array, dict]:
    """Capacity route + expert FFN + combine on (T, d) tokens (the
    dry-run's per-group and tensor-parallel dispatches)."""
    h, state, aux = _route(params, xt, cfg, C)
    out = _expert_ffn(params, h, cfg)
    return _combine(out, state, xt.dtype), aux


def _dispatch_ffn_ep(params: dict, xt: Array, cfg: ModelConfig, C: int,
                     model_axis: str) -> tuple[Array, dict]:
    """Expert-parallel dispatch inside shard_map: the canonical MoE
    all-to-all.  Weights hold E/m experts per chip; token buffers are
    exchanged over the model axis (split experts, concat capacity), the
    local experts run at full d_ff, and a reverse all-to-all brings the
    outputs home.  Collectives: exactly 2 x buffer bytes per layer."""
    h, state, aux = _route(params, xt, cfg, C)               # (E, C, d)
    # -> (E_loc, m*C, d): every chip receives its experts' tokens from
    # every model-rank of its data shard
    h = jax.lax.all_to_all(h, model_axis, split_axis=0, concat_axis=1,
                           tiled=True)
    out = _expert_ffn(params, h, cfg)
    out = jax.lax.all_to_all(out, model_axis, split_axis=1, concat_axis=0,
                             tiled=True)                     # (E, C, d)
    return _combine(out, state, xt.dtype), aux
