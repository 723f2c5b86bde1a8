"""Chip-compiler compiles of PROFET's main path at real widths.

Each test lowers one program for a described TPU v5e chip (no chip
attached) and compiles it with the TPU compiler, which refuses what the
chip would refuse: unsupported Mosaic gathers, misaligned blocks, too much
VMEM. Nothing runs, so these say nothing about results or times.

Shapes: the paper bank (4 devices, 12 pairs, 60 trees, up to 461 nodes,
33 features, 346 training cases) and the full-catalog bank over
``benchmarks.common.ALL_DEVICES`` (9 devices, 72 pairs, same widths), as a
fit of ``workloads.generate`` with ``ProfetConfig`` defaults grows them.
"""
import functools
import os

import numpy as np
import pytest

from repro.core import regressors
from repro.core.regressors import DNNRegressor, bucket
from repro.kernels import forest_eval

TREES, NODES, FEATURES, CASES = 60, 461, 33, 346
PAPER_PAIRS, CATALOG_PAIRS = 12, 72
MAX_WAVE = 64                      # LatencyService default wave size
WARMUP_ROWS = 2 * MAX_WAVE         # its default warm-up row cap


@pytest.fixture(scope="module")
def topo(tmp_path_factory):
    # describing the topology loads libtpu, which otherwise writes its
    # logs to a fixed directory under /tmp
    os.environ.setdefault("TPU_LOG_DIR",
                          str(tmp_path_factory.mktemp("tpu_logs")))
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out of it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(one_chip, shape, dtype):
    import jax
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("pairs", [PAPER_PAIRS, CATALOG_PAIRS],
                         ids=["paper", "catalog"])
def test_forest_kernel_compiles_for_v5e(one_chip, pairs):
    """The grouped forest launch at the largest block count the service's
    warm-up compiles for this bank."""
    import jax
    import jax.numpy as jnp
    L, S = forest_eval.LANES, forest_eval.SUBLANES
    n_blocks = bucket(min(WARMUP_ROWS, pairs + -(-WARMUP_ROWS // L)))
    tables = (pairs, -(-TREES // S) * S, -(-NODES // L) * L)
    args = [_spec(one_chip, (n_blocks,), jnp.int32),
            _spec(one_chip, (n_blocks,), jnp.int32),
            _spec(one_chip, (-(-FEATURES // S) * S, n_blocks * L),
                  jnp.float32)]
    args += [_spec(one_chip, tables, dt) for dt in
             (jnp.int32, jnp.float32, jnp.int32, jnp.int32, jnp.float32)]
    fn = functools.partial(forest_eval.grouped_leaf_values, interpret=False)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _head_params(one_chip, lead):
    import jax.numpy as jnp
    sizes = (FEATURES,) + DNNRegressor.LAYERS
    return [{"w": _spec(one_chip, lead + (sizes[i], sizes[i + 1]),
                        jnp.float32),
             "b": _spec(one_chip, lead + (sizes[i + 1],), jnp.float32)}
            for i in range(len(sizes) - 1)]


def test_stacked_mlp_apply_compiles_for_v5e(one_chip):
    """The bank's stacked MLP apply at the widest warm-up bucket of the
    paper bank: every head, a full wave's rows."""
    import jax.numpy as jnp
    g_pad = bucket(PAPER_PAIRS)
    r_pad = bucket(WARMUP_ROWS, DNNRegressor.PREDICT_BUCKET_MIN)
    compiled = regressors._mlp_apply_multi().lower(
        _head_params(one_chip, (PAPER_PAIRS,)),
        _spec(one_chip, (g_pad,), jnp.int32),
        _spec(one_chip, (g_pad, r_pad, FEATURES), jnp.float32)).compile()
    assert compiled.memory_analysis() is not None


def test_dnn_trainer_compiles_for_v5e(one_chip):
    """``fit_dnn_multi``'s scanned, target-vmapped Adam trainer at the paper
    fit's shapes: one anchor's 3 targets over every case, default epochs."""
    import jax.numpy as jnp
    from repro.core.predictor import ProfetConfig
    K = 3
    bs = min(128, CASES)
    steps = ProfetConfig.dnn_epochs * -(-CASES // bs)
    params = _head_params(one_chip, (K,))
    opt = {"m": params, "v": params,
           "t": _spec(one_chip, (K,), jnp.float32)}
    compiled = regressors._trainer().lower(
        params, opt, _spec(one_chip, (CASES, FEATURES), jnp.float32),
        _spec(one_chip, (K, CASES), jnp.float32),
        _spec(one_chip, (steps, bs), jnp.int32),
        _spec(one_chip, (), jnp.float32)).compile()
    assert compiled.memory_analysis() is not None
