"""The served path's device programs compile for a v5e chip at real widths.

The TPU compiler is installed with JAX, and it compiles for a chip that is
described rather than attached: these tests catch what interpret mode
cannot (a tile Mosaic refuses, a program that overflows the chip's
memory) without a chip. Nothing here runs a program.

The topology is described only inside the module fixture below: one
process at a time may load the TPU library, and only the worker that runs
this file should. Keep every described-chip compile in this one file.
"""
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

N_ROWS = 1 << 20          # cache rows: a deployment-scale corpus
DIM = 768                 # siso-embedder width
BATCH = 8
RESCORE_K = 16            # CacheConfig.rescore_k default


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler or library lock held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile is written to the persistent cache but can
    # never be read back without a chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled(lowered):
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()    # the Mosaic kernel
    return compiled


@pytest.mark.parametrize("k", [1, RESCORE_K])
def test_cosine_topk_compiles_for_v5e(one_chip, k):
    from repro.kernels.cosine_topk import ops
    f32 = jnp.float32
    _compiled(ops.cosine_topk.lower(
        _spec((BATCH, DIM), f32, one_chip), _spec((N_ROWS, DIM), f32, one_chip),
        k=k, valid=_spec((N_ROWS,), jnp.bool_, one_chip),
        theta=_spec((), f32, one_chip), early_exit=k == 1, return_hit=True,
        interpret=False))


def test_cosine_topk_tile_count_compiles_for_v5e(one_chip):
    """The served lookup's variant: top-1, early exit, the hit mask and the
    count of tiles computed."""
    from repro.kernels.cosine_topk import ops
    f32 = jnp.float32
    compiled = _compiled(ops.cosine_topk.lower(
        _spec((BATCH, DIM), f32, one_chip), _spec((N_ROWS, DIM), f32, one_chip),
        k=1, valid=_spec((N_ROWS,), jnp.bool_, one_chip),
        theta=_spec((), f32, one_chip), early_exit=True, return_hit=True,
        return_tiles=True, interpret=False))
    tiles = compiled.out_info[-1]
    assert tiles.shape == (2,) and tiles.dtype == jnp.int32


@pytest.mark.parametrize("k", [1, RESCORE_K])
def test_cosine_topk_q8_compiles_for_v5e(one_chip, k):
    from repro.kernels.cosine_topk import ops
    _compiled(ops.cosine_topk_q8.lower(
        _spec((BATCH, DIM), jnp.float32, one_chip),
        _spec((N_ROWS, DIM), jnp.int8, one_chip),
        _spec((N_ROWS,), jnp.float32, one_chip), k=k,
        valid=_spec((N_ROWS,), jnp.bool_, one_chip), interpret=False))


def test_embedder_encode_compiles_for_v5e(one_chip):
    """The jitted encoder at its published widths, at the serving bucket."""
    from repro.configs.base import get_config
    from repro.models import embedder
    cfg = get_config("siso-embedder")
    params = jax.eval_shape(partial(embedder.init_params, cfg=cfg),
                            jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: _spec(a.shape, a.dtype, one_chip),
                          params)
    compiled = jax.jit(partial(embedder.encode, cfg=cfg)).lower(
        params, tokens=_spec((BATCH, 16), jnp.int32, one_chip)).compile()
    out = compiled.out_info
    assert out.shape == (BATCH, cfg.d_model) and out.dtype == jnp.float32


@pytest.mark.parametrize("tokens", [1, 64])
def test_served_expert_layer_compiles_for_v5e(one_chip, tokens):
    """The served expert layer at deepseek-v2-236b's published widths, as
    one chip's share (routing group 0, 20 of the 160 experts it routes
    over), for a decode token and a 64-token prompt: dropless, so its
    buffer holds every token in every held expert."""
    from repro.configs.base import get_config
    from repro.models import layers as L
    cfg = get_config("deepseek-v2-236b")
    params = jax.eval_shape(partial(L.moe_init, cfg=cfg, dtype=jnp.bfloat16),
                            jax.random.PRNGKey(0))
    params = {k: (v if k in ("router", "shared")
                  else jax.ShapeDtypeStruct((20,) + v.shape[1:], v.dtype))
              for k, v in params.items()}
    params = jax.tree.map(lambda a: _spec(a.shape, a.dtype, one_chip),
                          params)
    compiled = jax.jit(partial(L.moe_serve, cfg=cfg)).lower(
        params, x=_spec((1, tokens, cfg.d_model), jnp.bfloat16,
                        one_chip)).compile()
    out, routed = compiled.out_info
    assert out.shape == (1, tokens, cfg.d_model)
    assert routed.shape == (20,) and routed.dtype == jnp.int32
