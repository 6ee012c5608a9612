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
pooling block would break.  The step's SGD update and the eval head are
plain XLA: their cases check that they compile with no custom call.

The topology is described inside a fixture (never at import), so only the
worker that runs this file loads the TPU library.

The last case compiles the engine's one-round program itself, at a tiny
size, and checks that each Pallas call of it lies under one of the two aggregation phases
(``repro.telemetry``): what a device trace's phase metrics read.
"""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import dispatch
from repro.kernels.coef_agg import coef_agg, coef_agg_pair
from repro.kernels.hieavg_agg import hieavg_agg

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
    "hieavg_agg": lambda: (
        _per_edge(hieavg_agg),
        ((N_EDGES, J, L_DENSE),) * 3 + ((N_EDGES, J),) * 4),
    "coef_agg": lambda: (
        _per_edge(coef_agg), ((N_EDGES, J, L_DENSE), (N_EDGES, J))),
    "coef_agg_pair": lambda: (
        _per_edge(coef_agg_pair),
        ((N_EDGES, J, L_DENSE),) * 2 + ((N_EDGES, J),) * 2),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    fn, shapes = CASES[name]()
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
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


#: Device slots a train step vmaps over: sec6's 25 devices and the
#: cross-device cohort's 50.
STEP_WIDTHS = (D, 2 * D)


@pytest.fixture(scope="module")
def compile_step(one_chip, no_persistent_cache):
    """``compile_step(d)``: one local SGD step of ``d`` devices x batch 32
    at DEFAULT widths, per-device weights, compiled as the engine
    compiles it for the chip; each width once."""
    from repro.fl.engine import train_epoch_body
    from repro.models import cnn_specs, init_from_specs

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.eval_shape(
        lambda: init_from_specs(cnn_specs(), jax.random.key(0)))
    step = functools.partial(train_epoch_body, kernel_mode="pallas")

    @functools.cache
    def compile_at(d):
        args = (jax.tree.map(lambda a: spec((d,) + a.shape, a.dtype),
                             params),
                spec((d, 1, B, HW, HW, 1)), spec((d, 1, B), jnp.int32),
                spec(()))
        return jax.jit(step).lower(*args).compile()
    return compile_at


@pytest.fixture(scope="module", params=STEP_WIDTHS, ids=lambda d: f"D{d}")
def train_step(request, compile_step):
    """The step at sec6's 25 devices and the cohort's 50: ``(d,
    compiled)``."""
    return request.param, compile_step(request.param)


def test_train_step_temporaries_fit_budget(train_step):
    """The step's temporaries stay under 2.5 GB at both widths (at D =
    25: 0.73 GB with XLA's convolution and the pool's mask residual; 1.54
    GB when the pool kept the conv's full-size output for its backward;
    the im2col patches around a Pallas matmul took 5.64 GB; 1.82 GB at D
    = 50)."""
    _, compiled = train_step
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5e9


#: The compiler's count of a step's bytes accessed, by width, plus 3%.
STEP_BYTES_BOUND = {D: 5.54e9, 2 * D: 9.72e9}


def test_train_step_bytes_accessed_bound(train_step):
    """The compiler's count of the step's HBM traffic stays within 3% of
    what it is with the pooling block's backward a product over the
    window view (``models.cnn.max_pool2x2``): 5.38e9 bytes at D = 25 and
    9.44e9 at D = 50.  Before, at D = 25 / 50: 5.78e9 / 11.15e9 when
    that backward stacked four tap planes (two concats and a relayout
    copy of dx); 7.08e9 at D = 25 before the SGD update fused into the
    weight gradients; 9.22e9 when the ReLU ran at full size before the
    pool and the pool's derivative reread the activation to count ties
    and place the gradient."""
    d, compiled = train_step
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert cost["bytes accessed"] < STEP_BYTES_BOUND[d]


def _eval(one_chip, points):
    """The engine's eval of the global model on the 1000 test images at
    DEFAULT widths; with ``points``, a sweep's eval, vmapped over its
    points' models with the test set shared."""
    from repro.fl.engine import eval_accuracy
    from repro.models import cnn_specs, init_from_specs

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lead = (points,) if points else ()
    params = jax.eval_shape(
        lambda: init_from_specs(cnn_specs(), jax.random.key(0)))
    args = (jax.tree.map(lambda a: spec(lead + a.shape, a.dtype), params),
            spec((N_TEST, HW, HW, 1)), spec((N_TEST,), jnp.int32))
    fn = functools.partial(eval_accuracy, kernel_mode="pallas")
    if points:
        fn = jax.vmap(fn, in_axes=(0, None, None))
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("name", ["sgd_update", "eval_head",
                                  "eval_head_3_points"])
def test_step_and_eval_compile_without_pallas_for_v5e(name, request,
                                                      one_chip,
                                                      no_persistent_cache):
    """The train step (its SGD update inline) and the eval head, one
    model's and a 3-point sweep's, compile for the chip as plain XLA: no
    Pallas custom call."""
    if name == "sgd_update":
        compiled = request.getfixturevalue("compile_step")(D)
    else:
        compiled = _eval(one_chip, 3 if name.endswith("3_points") else None)
    assert "tpu_custom_call" not in compiled.as_text()


def test_engine_pallas_calls_lie_under_one_phase(one_chip,
                                                 no_persistent_cache):
    """Each Pallas custom call of the one-round ``run_engine_chunk``,
    compiled for the described chip, names exactly one round phase in its
    ``op_name``, and that phase is one of the two aggregations: train and
    eval run no Pallas kernel.  The lowered text cannot show it: there
    each kernel sits in a function of its own, whose locations name only
    the kernel."""
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
    assert {ph[0] for ph in calls} == {telemetry.EDGE_AGG,
                                       telemetry.GLOBAL_AGG}
    # XLA may merge instructions and their op_names; none names two phases
    assert all(len(phases(ln)) <= 1 for ln in lines)
