"""Every kernel of the engine's main path compiles for a TPU v5e.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology.  Shapes are the paper's DEFAULT widths (28x28 inputs, 32/64
conv channels, N = J = 5 edges x devices, batch 32, 1000 test images),
batched the way the engine calls each kernel.  This catches what the
Pallas interpreter cannot: block shapes the TPU tiling refuses and
kernels that outgrow VMEM.  The CNN conv block is no Pallas kernel but
XLA's own convolution: its cases check that it compiles to one with no
custom call around it, and that a whole train step stays within a
temporary-memory budget that the 9x im2col patch buffer would break and
within a bound on its bytes accessed that extra full-size passes of the
pooling block would break.

The topology is described inside a fixture (never at import), so only the
worker that runs this file loads the TPU library.

The last case compiles the engine's one-round program itself, at a tiny
size, and checks that each Pallas call of it lies under one round phase
(``repro.telemetry.PHASES``): what a device trace's phase metrics read.
"""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import dispatch
from repro.kernels.coef_agg import coef_agg, coef_agg_pair
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


def _per_edge(fn):
    return jax.vmap(functools.partial(fn, interpret=False))


CASES = {
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


def _conv(cin, cout):
    """The conv block vmapped over devices, each with its own weights, as
    the engine's train step runs it on a TPU."""
    def fwd(x, w, b):
        return jax.vmap(functools.partial(dispatch.conv3x3_bias_relu,
                                          mode="pallas"))(x, w, b)
    return fwd, ((D, B, HW, HW, cin), (D, 3, 3, cin, cout), (D, cout))


def _conv_grad(cin, cout):
    fwd, shapes = _conv(cin, cout)
    return jax.grad(lambda x, w, b: jnp.sum(fwd(x, w, b)),
                    argnums=(0, 1, 2)), shapes


CONV_CASES = {
    "conv3x3_fwd_cin1": lambda: _conv(1, C1),
    "conv3x3_bwd_cin1": lambda: _conv_grad(1, C1),
    "conv3x3_fwd_cin32": lambda: _conv(C1, C2),
    "conv3x3_bwd_cin32": lambda: _conv_grad(C1, C2),
}


@pytest.mark.parametrize("name", sorted(CONV_CASES))
def test_conv_block_compiles_to_xla_convolution_for_v5e(
        name, one_chip, no_persistent_cache):
    fn, shapes = CONV_CASES[name]()
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "convolution(" in text
    assert "tpu_custom_call" not in text


@pytest.fixture(scope="module")
def train_step(one_chip, no_persistent_cache):
    """One local SGD step of sec6's 25 devices x batch 32 at DEFAULT
    widths, per-device weights, as the engine compiles it for the chip."""
    from repro.fl.engine import train_epoch_body
    from repro.models import cnn_specs, init_from_specs

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.eval_shape(
        lambda: init_from_specs(cnn_specs(), jax.random.key(0)))
    args = (jax.tree.map(lambda a: spec((D,) + a.shape, a.dtype), params),
            spec((D, 1, B, HW, HW, 1)), spec((D, 1, B), jnp.int32),
            spec(()))
    step = functools.partial(train_epoch_body, kernel_mode="pallas")
    return jax.jit(step).lower(*args).compile()


def test_train_step_temporaries_fit_budget(train_step):
    """The step's temporaries stay under 2.5 GB (0.92 GB with XLA's
    convolution and the pool's mask residual; 1.54 GB when the pool
    kept the conv's full-size output for its backward; the im2col
    patches around a Pallas matmul took 5.64 GB)."""
    assert train_step.memory_analysis().temp_size_in_bytes < 2.5e9


def test_train_step_bytes_accessed_bound(train_step):
    """The compiler's count of the step's HBM traffic stays under 8.4e9
    bytes: 7.08e9 with the pooling block's one full-size pass each way
    (``models.cnn.max_pool2x2``), 9.22e9 when the ReLU ran at full size
    before the pool and the pool's derivative reread the activation to
    count ties and place the gradient."""
    cost = train_step.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert cost["bytes accessed"] < 8.4e9


def test_engine_pallas_calls_lie_under_one_phase(one_chip,
                                                 no_persistent_cache):
    """Each Pallas custom call of the one-round ``run_engine_chunk``,
    compiled for the described chip, names exactly one round phase in its
    ``op_name``.  The lowered text cannot show it: there each kernel sits
    in a function of its own, whose locations name only the kernel."""
    from repro import telemetry
    from repro.configs.bhfl_cnn import REDUCED
    from repro.fl import BHFLSimulator, engine

    setting = dataclasses.replace(REDUCED, t_global_rounds=3, n_edges=2,
                                  j_per_edge=3, image_hw=8)
    sim = BHFLSimulator(setting, n_train=120, n_test=40, steps_per_epoch=2)
    inp = engine.build_inputs(sim)
    args = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        (engine.slice_rounds(inp, 0, 1), engine.init_engine_carry(inp),
         jnp.int32(0)))
    text = engine.run_engine_chunk.lower(
        *args, kernel_mode="pallas").compile().as_text()

    def phases(line):
        op = re.search(r'op_name="([^"]*)"', line)
        return [p for p in telemetry.PHASES if op and p in op.group(1)]

    lines = text.splitlines()
    calls = [phases(ln) for ln in lines
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls and all(len(ph) == 1 for ph in calls), calls
    assert {ph[0] for ph in calls} == set(telemetry.PHASES)
    # XLA may merge instructions and their op_names; none names two phases
    assert all(len(phases(ln)) <= 1 for ln in lines)
