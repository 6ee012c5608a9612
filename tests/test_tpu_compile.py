"""Every Pallas kernel of the engine's main path compiles for a TPU v5e.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology.  Shapes are the paper's DEFAULT widths (28x28 inputs, 32/64
conv channels, N = J = 5 edges x devices, batch 32, 1000 test images),
batched the way the engine calls each kernel.  This catches what the
Pallas interpreter cannot: block shapes the TPU tiling refuses and
kernels that outgrow VMEM.

The topology is described inside a fixture (never at import), so only the
worker that runs this file loads the TPU library.
"""
import functools

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.coef_agg import coef_agg, coef_agg_pair
from repro.kernels.conv3x3 import conv3x3_bias_relu
from repro.kernels.eval_head import eval_head
from repro.kernels.hieavg_agg import hieavg_agg
from repro.kernels.sgd_update import sgd_update

D, B, HW, C1, C2, NCLS = 25, 32, 28, 32, 64, 10   # DEFAULT widths
N_EDGES, J = 5, 5
DENSE = (HW // 2) ** 2 * C2                        # 12544 pooled features
L_DENSE = DENSE * NCLS                             # 125440 dense weights
N_TEST = 1000


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                          # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache, so keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _conv(cin, cout):
    def fwd(x, w, b):
        return jax.vmap(functools.partial(conv3x3_bias_relu,
                                          interpret=False),
                        in_axes=(0, None, None))(x, w, b)
    return fwd, ((D, B, HW, HW, cin), (3, 3, cin, cout), (cout,))


def _conv_grad(cin, cout):
    fwd, shapes = _conv(cin, cout)
    return jax.grad(lambda x, w, b: jnp.sum(fwd(x, w, b)),
                    argnums=(0, 1, 2)), shapes


def _per_edge(fn):
    return jax.vmap(functools.partial(fn, interpret=False))


CASES = {
    "conv3x3_fwd_cin1": lambda: _conv(1, C1),
    "conv3x3_bwd_cin1": lambda: _conv_grad(1, C1),
    "conv3x3_fwd_cin32": lambda: _conv(C1, C2),
    "conv3x3_bwd_cin32": lambda: _conv_grad(C1, C2),
    "sgd_update": lambda: (
        functools.partial(sgd_update, interpret=False),
        ((D, L_DENSE), (D, L_DENSE), ())),
    "hieavg_agg": lambda: (
        _per_edge(hieavg_agg),
        ((N_EDGES, J, L_DENSE),) * 3 + ((N_EDGES, J),) * 4),
    "coef_agg": lambda: (
        _per_edge(coef_agg), ((N_EDGES, J, L_DENSE), (N_EDGES, J))),
    "coef_agg_pair": lambda: (
        _per_edge(coef_agg_pair),
        ((N_EDGES, J, L_DENSE),) * 2 + ((N_EDGES, J),) * 2),
    "eval_head": lambda: (
        lambda f, w, b, y: eval_head(f, w, b, y, interpret=False),
        ((N_TEST, DENSE), (DENSE, NCLS), (NCLS,), (N_TEST,))),
    # a 3-point sweep vmaps the eval over its points' models
    "eval_head_3_points": lambda: (
        jax.vmap(lambda f, w, b, y: eval_head(f, w, b, y, interpret=False),
                 in_axes=(0, 0, 0, None)),
        ((3, N_TEST, DENSE), (3, DENSE, NCLS), (3, NCLS), (N_TEST,))),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    fn, shapes = CASES[name]()
    # eval_head's fourth operand is the int32 label vector
    dtypes = [jnp.int32 if name.startswith("eval_head") and i == 3
              else jnp.float32 for i in range(len(shapes))]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in zip(shapes, dtypes)]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
