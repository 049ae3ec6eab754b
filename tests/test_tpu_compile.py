"""Compile the serving path's kernels for a described TPU v5e chip.

Nothing here runs on a chip: the TPU compiler, which is installed with
JAX, compiles for a topology that is described and not attached, and
refuses what Mosaic would refuse on the chip (block shapes off the (8, 128)
tiling, too much VMEM).  The interpret-mode sweeps in ``test_kernels.py``
cannot see those faults.  Shapes are the dndm-text8 serving shapes: batch
8, canvas 256, vocab 28, and a 32000-token vocab for a multi-tile walk.

The topology is described inside a module fixture, never at import time,
so every xdist worker collects the same tests and only the worker that
runs this file loads the TPU library.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_scores import ops as ds_ops
from repro.kernels.dndm_update import ops as dndm_ops
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.moe_gmm import ops as gmm_ops
from repro.kernels.moe_gmm.kernel import moe_gmm
from repro.kernels.moe_route import ops as route_ops

B, N = 8, 256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    cache_on = jax.config.jax_enable_compilation_cache
    # compiles for a described chip cannot be read back without one
    jax.config.update("jax_enable_compilation_cache", False)
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype,
                                                    sharding=one_chip)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("K", [28, 32000])
@pytest.mark.parametrize("mode", ["argmax", "gumbel"])
def test_dndm_update_compiles(shape, mode, K):
    logits = shape((B, N, K), jnp.float32)
    tokens = shape((B, N), jnp.int32)
    if mode == "argmax":
        _compile(lambda lg, x, tau: dndm_ops.dndm_update(
            lg, x, tau, 3, interpret=False), logits, tokens, tokens)
    else:
        _compile(lambda lg, g, x, tau: dndm_ops.dndm_update(
            lg, x, tau, 3, gumbel=g, version=2, interpret=False),
            logits, logits, tokens, tokens)


@pytest.mark.parametrize("K", [28, 32000])
@pytest.mark.parametrize("mode", ["argmax", "gumbel"])
def test_decode_scores_compiles(shape, mode, K):
    logits = shape((B, N, K), jnp.float32)
    if mode == "argmax":
        _compile(lambda lg: ds_ops.decode_scores(lg, interpret=False),
                 logits)
    else:
        _compile(lambda lg, g: ds_ops.decode_scores(
            lg, gumbel=g, temperature=0.7, interpret=False), logits, logits)


@pytest.mark.parametrize("block_n,block_v", [(8, 64), (200, 100)])
def test_decode_kernels_compile_at_any_caller_block(shape, block_n,
                                                    block_v):
    """Compiled mode rounds the caller's blocks onto the (8, 128) tiling,
    whatever block_n / block_v it is given."""
    logits = shape((B, N, 28), jnp.float32)
    tokens = shape((B, N), jnp.int32)
    _compile(lambda lg, x, tau: dndm_ops.dndm_update(
        lg, x, tau, 3, block_n=block_n, block_v=block_v, interpret=False),
        logits, tokens, tokens)
    _compile(lambda lg: ds_ops.decode_scores(
        lg, block_n=block_n, block_v=block_v, interpret=False), logits)


def test_flash_attention_compiles(shape):
    q = shape((B, N, 12, 64), jnp.float32)
    bias = shape((B, N, N), jnp.float32)
    _compile(lambda q, k, v, b: fa_ops.flash_attention(
        q, k, v, b, interpret=False), q, q, q, bias)


@pytest.mark.parametrize("method", ["dndm", "dndm_topk"])
def test_stepwise_step_compiles_at_text8_width(shape, method, monkeypatch):
    """One continuous-batching step of dndm-text8 (12 layers, d_model 768)
    at batch 8, canvas 256: the decode kernel is in the program and the
    weights are its arguments, not constants baked into it."""
    import repro.configs as configs_lib
    from repro.core.samplers import registry
    from repro.models.model import Model
    from repro.serving import EngineConfig, GenerationEngine

    # steer the decode backend to the compiled kernel on this CPU host
    monkeypatch.setenv("REPRO_DECODE_BACKEND", "pallas")
    model = Model(configs_lib.get("dndm-text8"))
    params = jax.tree.map(lambda a: shape(a.shape, a.dtype),
                          jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    engine = GenerationEngine(model, params, EngineConfig(method=method))
    rt = engine.runtime()
    state = {"x": shape((B, N), jnp.int32),
             "revealed": shape((B, N), jnp.bool_)}
    compiled = _compile(
        lambda s, tau, t, k, c: registry.get(method).stepwise_step(
            s, tau, t, k, c, rt),
        state, shape((B, N), jnp.int32), shape((B,), jnp.int32),
        shape((B, 2), jnp.uint32), engine.call_cond(None))
    weight_bytes = sum(a.size * a.dtype.itemsize
                       for a in jax.tree.leaves(params))
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= weight_bytes
    assert mem.generated_code_size_in_bytes < weight_bytes // 10


@pytest.mark.parametrize("k,n,swiglu", [(2048, 768, True),
                                         (768, 2048, False)])
def test_moe_gmm_compiles_at_sdar_widths(shape, k, n, swiglu):
    """SDAR-30B-A3B's held experts on one chip: 16 experts, the fused
    gate and up SwiGLU (2048 -> 768) and the down matmul (768 -> 2048)
    over the dropless buffer of batch 8 x canvas 256 x top-8 rows.  The op
    carries the kernel's name, which the benchmark's readers look for."""
    rows = B * N * 8
    weight = shape((16, k, n), jnp.bfloat16)
    args = (shape((rows, k), jnp.bfloat16), weight, shape((16,), jnp.int32))
    if swiglu:
        compiled = _compile(lambda a, w, g, u: moe_gmm(a, w, g, u),
                            *args, weight)
    else:
        compiled = _compile(lambda a, w, g: moe_gmm(a, w, g), *args)
    assert "%moe_gmm" in compiled.as_text()


@pytest.mark.parametrize("op", ["moe_dispatch", "moe_combine"])
def test_moe_route_compiles_at_sdar_widths(shape, op):
    """The routing's row movement at SDAR-30B-A3B's widths on one chip:
    batch 8 x canvas 256 tokens of width 2048, top-8, so a buffer of
    16,384 pairs of which the 16 held experts' come first.  Each op
    carries its name, which must not be the grouped matmul's."""
    T, k, d = B * N, 8, 2048
    pairs = shape((T * k,), jnp.int32)
    count = shape((), jnp.int32)
    if op == "moe_dispatch":
        compiled = _compile(lambda x, o, n: route_ops.dispatch(
            x, o, n, k, False), shape((T, d), jnp.bfloat16), pairs, count)
    else:
        compiled = _compile(lambda out, g, o, h, n: route_ops.combine(
            out, g, o, h, n, jnp.bfloat16, False),
            shape((T * k, d), jnp.bfloat16), shape((T, k), jnp.float32),
            pairs, shape((T * k,), jnp.bool_), count)
    assert f"%{op}" in compiled.as_text()


@pytest.fixture
def sdar_two_layers(shape, monkeypatch):
    """SDAR-30B-A3B at its widths with two layers: one chip's 16 held
    experts, the grouped matmuls steered to the compiled kernel."""
    from repro import configs
    from repro.models.config import moe_pattern
    from repro.models.model import Model
    monkeypatch.setattr(gmm_ops, "use_kernel", lambda: True)
    monkeypatch.setattr(gmm_ops, "default_interpret", lambda: False)
    monkeypatch.setattr(route_ops, "default_interpret", lambda: False)
    cfg = configs.get("sdar-30b-a3b").replace(
        n_layers=2, block_pattern=moe_pattern(2), held_experts=16)
    model = Model(cfg)
    layout = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return model, jax.tree.map(lambda a: shape(a.shape, a.dtype), layout)


def test_sdar_experts_read_in_place(shape, sdar_two_layers):
    """The layer scan hands the kernel the stacked expert weights: no
    operation of the compiled forward makes a per-layer copy of a held
    expert weight (before, three such copies a layer took a fifth of the
    step on the chip)."""
    model, params = sdar_two_layers
    compiled = _compile(
        lambda p, x: model.forward(p, x, None, causal=False)[0],
        params, shape((B, N), jnp.int32))
    text = compiled.as_text()
    assert "%moe_gmm" in text
    copies = [line for line in text.splitlines()
              if re.search(r"= bf16\[(1,)?16,(2048,768|768,2048)\]", line)
              and "parameter(" not in line]
    assert copies == []


def test_sdar_forward_moves_only_held_rows(shape, sdar_two_layers):
    """The routing moves the held pairs' rows in its two kernels: no
    gather or fusion of the compiled forward yields the (16384, 2048)
    pair buffer (before, one filled it and one read it back, a fifth of
    the step on the chip)."""
    model, params = sdar_two_layers
    compiled = _compile(
        lambda p, x: model.forward(p, x, None, causal=False)[0],
        params, shape((B, N), jnp.int32))
    text = compiled.as_text()
    assert "%moe_dispatch" in text and "%moe_combine" in text
    whole = [line for line in text.splitlines()
             if re.search(r"= bf16\[16384,2048\]\S* (gather|fusion)\(",
                          line)]
    assert whole == []


def test_sdar_gradient_compiles(shape, sdar_two_layers):
    """A training step's gradient through the kernels compiles for the
    chip (the backward passes are ``ragged_dot``'s and the XLA gather's
    and combine's, by the custom VJPs)."""
    model, params = sdar_two_layers

    def loss(p, x):
        logits, aux = model.forward(p, x, None, causal=False)
        return (jnp.mean(jax.nn.log_softmax(
            logits.astype(jnp.float32))[..., 0]) + aux["load_balance"])

    compiled = _compile(jax.value_and_grad(loss), params,
                        shape((2, 128), jnp.int32))
    text = compiled.as_text()
    assert all(f"%{op}" in text
               for op in ("moe_gmm", "moe_dispatch", "moe_combine"))
