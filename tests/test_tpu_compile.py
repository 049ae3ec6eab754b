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
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_scores import ops as ds_ops
from repro.kernels.dndm_update import ops as dndm_ops
from repro.kernels.flash_attention import ops as fa_ops

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
