"""Kernel plane: backend dispatch, fused-kernel engine parity, donation.

Three pin groups (see docs/ARCHITECTURE.md §Kernel plane):

  * kernel oracles — ``hieavg_agg`` / ``sgd_update`` against their
    pure-jnp refs across tile-tail shapes (L not a multiple of TILE,
    L < TILE) and the mixed-dtype bf16 ``history_dtype`` layout,
  * engine parity — ``kernel_mode="interpret"`` (the fused kernels
    through the Pallas interpreter, the only kernel execution CPU has)
    must reproduce the pure-XLA engine on standalone runs AND across a
    padded multi-bucket sweep grid; the 4-device shard_map pin lives in
    ``test_multidevice_sweep.py``,
  * donation — the donated engine/sweep entries return the same numbers
    as the non-donated ones, never consume the shared data plane, and a
    donated plan is consumed exactly once.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.configs.bhfl_cnn import REDUCED
from repro.core import baselines, hieavg
from repro.fl import BHFLSimulator, build_inputs, plan_sweep, run_plan, \
    run_sweep
from repro.fl.engine import (SHARED_DATA_FIELDS, run_engine,
                             run_engine_donated, split_inputs)
from repro.kernels import dispatch as kd
from repro.kernels.coef_agg import TILE as CTILE
from repro.kernels.coef_agg import coef_agg, coef_agg_pair
from repro.kernels.eval_head import eval_head
from repro.kernels.ops import (fused_edge_aggregate_batched,
                               fused_mix_and_update)
from repro.kernels.ref import (coef_agg_pair_ref, coef_agg_ref,
                               conv3x3_bias_relu_ref, eval_head_ref,
                               sgd_update_ref)
from repro.kernels.sgd_update import TILE, sgd_update

TINY = dataclasses.replace(REDUCED, t_global_rounds=3, n_edges=3,
                           j_per_edge=3, image_hw=8)
KW = dict(n_train=300, n_test=100, steps_per_epoch=2)


def _sim(kernel_mode="auto", **kw):
    return BHFLSimulator(TINY, "hieavg", "temporary", "temporary",
                         kernel_mode=kernel_mode, **KW, **kw)


def _close(a, b, *, acc_atol=1e-6):
    np.testing.assert_allclose(b.accuracy, a.accuracy, atol=acc_atol)
    np.testing.assert_allclose(b.loss, a.loss, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(b.grad_norm, a.grad_norm, rtol=1e-4,
                               atol=1e-6)


# ---------------------------------------------------------------- dispatch
def test_resolve_kernel_mode_cpu_auto_is_xla():
    """On CPU "auto" must pick the XLA reference — never the interpreter
    (the satellite bugfix: nothing ever 'flips interpret off', so the
    default has to be backend detection, and CPU has no Pallas backend)."""
    assert jax.default_backend() == "cpu"
    assert kd.resolve_kernel_mode("auto") == "xla"
    assert kd.default_interpret() is True
    for mode in ("pallas", "interpret", "xla"):
        assert kd.resolve_kernel_mode(mode) == mode


def test_unknown_kernel_mode_raises_naming_the_choices():
    with pytest.raises(ValueError, match="auto"):
        kd.resolve_kernel_mode("mosaic")
    with pytest.raises(ValueError, match="kernel_mode"):
        BHFLSimulator(TINY, kernel_mode="nope", **KW)
    with pytest.raises(ValueError, match="kernel_mode"):
        run_sweep(TINY, kernel_mode="nope", **KW)


# ----------------------------------------------------------- kernel oracles
# Every test in this group is marked ``kernel_oracle``: CI runs them as a
# dedicated interpret-mode oracle-parity job (`pytest -m kernel_oracle`).
@pytest.mark.kernel_oracle
@settings(max_examples=10, deadline=None)
@given(n=st.integers(1, 9),
       l=st.sampled_from([1, 7, 100, TILE - 1, TILE, TILE + 1, 3 * TILE]),
       seed=st.integers(0, 99))
def test_sgd_update_matches_ref_on_tile_tails(n, l, seed):
    ks = jax.random.split(jax.random.key(seed), 2)
    w = jax.random.normal(ks[0], (n, l))
    g = jax.random.normal(ks[1], (n, l))
    got = sgd_update(w, g, jnp.float32(0.37), interpret=True)
    ref = sgd_update_ref(w, g, 0.37)
    # 1-ulp slack: XLA may contract the multiply-subtract into an FMA in
    # one lowering and not the other
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-6, atol=1e-7)


@pytest.mark.kernel_oracle
def test_sgd_update_zero_scale_is_exact_identity():
    """scale = lr x step-validity: a padded sweep step (0) must be an
    exact no-op, bitwise."""
    w = jax.random.normal(jax.random.key(0), (4, 333))
    g = jax.random.normal(jax.random.key(1), (4, 333)) * 1e3
    got = sgd_update(w, g, jnp.float32(0.0), interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(w))


@pytest.mark.kernel_oracle
def test_sgd_update_bf16_storage():
    w = jax.random.normal(jax.random.key(0), (3, 100), jnp.bfloat16)
    g = jax.random.normal(jax.random.key(1), (3, 100), jnp.bfloat16)
    got = sgd_update(w, g, jnp.float32(0.1), interpret=True)
    assert got.dtype == jnp.bfloat16
    ref = sgd_update_ref(w, g, 0.1)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), rtol=1e-6,
                               atol=1e-7)


@pytest.mark.kernel_oracle
@pytest.mark.parametrize("l", [1, 40, TILE + 3])
def test_hieavg_agg_mixed_history_dtype(l):
    """The engine's ``history_dtype`` layout: f32 submissions, bf16
    history leaves — each kernel output casts back to its own operand's
    dtype (the history stays bf16, the aggregate stays f32)."""
    from repro.kernels.hieavg_agg import hieavg_agg
    from repro.kernels.ref import hieavg_agg_ref

    n = 5
    ks = jax.random.split(jax.random.key(3), 5)
    w = jax.random.normal(ks[0], (n, l))
    prev = jax.random.normal(ks[1], (n, l), jnp.bfloat16)
    dmean = (jax.random.normal(ks[2], (n, l)) * 0.1).astype(jnp.bfloat16)
    mask = jax.random.bernoulli(ks[3], 0.6, (n,))
    cp = jax.random.uniform(ks[4], (n,))
    ce = (1.0 - cp) * 0.3
    nobs = jnp.arange(n, dtype=jnp.float32)
    ref = hieavg_agg_ref(w, prev, dmean, mask, cp, ce, nobs)
    got = hieavg_agg(w, prev, dmean, mask, cp, ce, nobs, interpret=True)
    assert got[0].dtype == jnp.float32
    assert got[1].dtype == got[2].dtype == jnp.bfloat16
    for r, g in zip(ref, got):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(r, np.float32), atol=6e-2)


@pytest.mark.kernel_oracle
def test_fused_batched_matches_core_batched_with_padding():
    """The engine's dense-layer entry: fused [N, J] aggregation ==
    ``hieavg.edge_aggregate_batched`` on a validity-masked layout with
    garbage in the padded slots, traced gamma/lam."""
    n_edges, j = 3, 4
    ks = jax.random.split(jax.random.key(0), 3)
    w = {"a": jax.random.normal(ks[0], (n_edges, j, 5, 3)),
         "b": jax.random.normal(ks[1], (n_edges, j, 17))}
    valid = jnp.asarray([[1, 1, 1, 0], [1, 1, 0, 0], [1, 1, 1, 1]], bool)
    mask = jax.random.bernoulli(ks[2], 0.6, (n_edges, j)) & valid
    hist = hieavg.init_history_batched(w)
    w1 = jax.tree.map(lambda x: x * 1.1 + 0.1, w)
    hist = hieavg.update_history_batched(hist, w1, valid)
    g0, lam = jnp.float32(0.9), jnp.float32(0.8)
    for normalize in (False, True):
        a_ref, h_ref = hieavg.edge_aggregate_batched(
            w1, mask, hist, valid, g0, lam, normalize)
        a_got, h_got = fused_edge_aggregate_batched(
            w1, mask, hist, valid, g0, lam, normalize, interpret=True)
        for k in w:
            np.testing.assert_allclose(np.asarray(a_got[k]),
                                       np.asarray(a_ref[k]), atol=1e-6)
            np.testing.assert_allclose(np.asarray(h_got.prev_w[k]),
                                       np.asarray(h_ref.prev_w[k]),
                                       atol=1e-6)
            np.testing.assert_allclose(np.asarray(h_got.delta_mean[k]),
                                       np.asarray(h_ref.delta_mean[k]),
                                       atol=1e-6)
        np.testing.assert_array_equal(np.asarray(h_got.n_obs),
                                      np.asarray(h_ref.n_obs))


@pytest.mark.kernel_oracle
def test_fused_global_matches_core_traced_weights():
    """Eq. (5) with J-weighted traced part weights — the engine's global
    layer call."""
    n = 3
    w = {"p": jax.random.normal(jax.random.key(9), (n, 7, 2))}
    hist = hieavg.init_history(w)
    hist = hieavg.update_history(hist, jax.tree.map(lambda x: x * 1.1, w),
                                 jnp.ones(n, bool))
    j_arr = jnp.asarray([3.0, 2.0, 4.0])
    pw = j_arr / jnp.sum(j_arr)
    mask = jnp.asarray([True, False, True])
    a_ref, _ = hieavg.aggregate(w, mask, hist, pw, jnp.float32(0.9),
                                jnp.float32(0.9), True)
    a_got, _ = fused_mix_and_update(w, mask, hist, pw, jnp.float32(0.9),
                                    jnp.float32(0.9), True, interpret=True)
    np.testing.assert_allclose(np.asarray(a_got["p"]),
                               np.asarray(a_ref["p"]), atol=1e-6)


# ------------------------------------------------- conv / eval / coef oracles
def _conv_block(x, w, b):
    """The TPU path's conv block (XLA's own convolution), run on the CPU."""
    return kd.conv3x3_bias_relu(x, w, b, mode="interpret")


@pytest.mark.kernel_oracle
@pytest.mark.parametrize("lead,hw,cin,cout", [
    ((1,), 5, 1, 3),     # one image, one input channel
    ((2,), 12, 4, 8),
    ((2,), 16, 3, 7),    # odd cout
    ((2, 3), 7, 2, 5),   # two leading batch dims, folded into N
])
def test_conv3x3_matches_ref_on_odd_shapes(lead, hw, cin, cout):
    """The conv block against the im2col oracle on odd spatial extents,
    channel counts and leading batch dims."""
    ks = jax.random.split(jax.random.key(0), 3)
    x = jax.random.normal(ks[0], lead + (hw, hw, cin))
    w = jax.random.normal(ks[1], (3, 3, cin, cout)) * 0.3
    bb = jax.random.normal(ks[2], (cout,)) * 0.3
    got = _conv_block(x, w, bb)
    ref = conv3x3_bias_relu_ref(x, w, bb)
    assert got.shape == ref.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)


@pytest.mark.kernel_oracle
def test_conv3x3_grads_match_ref():
    """XLA's transpose of the convolution: dx, dw and db against the
    pure-jnp reference's autodiff."""
    ks = jax.random.split(jax.random.key(1), 4)
    x = jax.random.normal(ks[0], (2, 9, 9, 3))
    w = jax.random.normal(ks[1], (3, 3, 3, 5)) * 0.3
    b = jax.random.normal(ks[2], (5,)) * 0.3
    dy = jax.random.normal(ks[3], (2, 9, 9, 5))

    def loss(fn):
        return lambda x, w, b: jnp.sum(fn(x, w, b) * dy)

    gx, gw, gb = jax.grad(loss(_conv_block), argnums=(0, 1, 2))(x, w, b)
    rx, rw, rb = jax.grad(loss(conv3x3_bias_relu_ref),
                          argnums=(0, 1, 2))(x, w, b)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx), atol=1e-4)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(rw), atol=1e-4)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(rb), atol=1e-4)


@pytest.mark.kernel_oracle
@pytest.mark.parametrize("shape", [(2, 9, 9, 3), (2, 3, 8, 8, 1),
                                   (1, 5, 7, 4)])
def test_im2col_col2im_vjp_matches_autodiff(shape):
    """``im2col3x3``'s hand-written col2im equals XLA's transpose of the
    plain pad + slice + concatenate, on leading batch dims and odd
    spatial extents."""
    from repro.models.cnn import im2col3x3

    def plain(x):
        h, wd = x.shape[-3], x.shape[-2]
        xp = jnp.pad(x, [(0, 0)] * (x.ndim - 3) + [(1, 1), (1, 1), (0, 0)])
        return jnp.concatenate([xp[..., i:i + h, j:j + wd, :]
                                for i in range(3) for j in range(3)], -1)

    ks = jax.random.split(jax.random.key(7), 2)
    x = jax.random.normal(ks[0], shape)
    cols, vjp = jax.vjp(im2col3x3, x)
    ref_cols, ref_vjp = jax.vjp(plain, x)
    np.testing.assert_array_equal(np.asarray(cols), np.asarray(ref_cols))
    dcols = jax.random.normal(ks[1], cols.shape)
    np.testing.assert_allclose(np.asarray(vjp(dcols)[0]),
                               np.asarray(ref_vjp(dcols)[0]), atol=1e-5)


def _bits(a):
    return np.asarray(a).view(np.uint32)


#: Inputs of the pooling pins: random; rounded to a coarse grid, full of
#: positive ties and all-zero windows; the same with two leading batch
#: dims.  (shape, coarse)
POOL_CASES = {"random": ((3, 8, 8, 5), False),
              "coarse_grid": ((3, 8, 8, 5), True),
              "two_leading_dims": ((2, 3, 8, 8, 5), True)}


def _assert_ties(y):
    """``y`` has windows whose positive max two taps share, and windows
    whose max is at most 0 (all zero after a ReLU)."""
    h, w, c = y.shape[-3:]
    win = np.asarray(y).reshape(-1, h // 2, 2, w // 2, 2, c)
    m = win.max(axis=(2, 4))
    count = (win == m[:, :, None, :, None, :]).sum(axis=(2, 4))
    assert np.any((count > 1) & (m > 0)) and np.any(m <= 0)


def _pool_input(key, case):
    shape, coarse = POOL_CASES[case]
    x = jax.random.normal(key, shape)
    return jnp.round(x * 2) / 2 if coarse else x


@pytest.mark.kernel_oracle
@pytest.mark.parametrize("case", sorted(POOL_CASES))
def test_max_pool2x2_vjp_equals_pool_flatten_to_the_bit(case):
    """The fused path's pool against ``_pool_flatten``'s reshape pool:
    the pooled values and the input gradient (a tie's gradient split over
    its taps) equal to the bit, signed zeros included."""
    from repro.models.cnn import _pool_flatten, max_pool2x2

    ks = jax.random.split(jax.random.key(12), 2)
    x = _pool_input(ks[0], case)
    if POOL_CASES[case][1]:
        _assert_ties(x)
    lead = x.shape[:-3]
    oracle = _pool_flatten
    for _ in lead[1:]:
        oracle = jax.vmap(oracle)
    out, vjp = jax.vjp(lambda v: max_pool2x2(v).reshape(lead + (-1,)), x)
    ref, ref_vjp = jax.vjp(oracle, x)
    g = jax.random.normal(ks[1], ref.shape)
    np.testing.assert_array_equal(_bits(out), _bits(ref))
    np.testing.assert_array_equal(_bits(vjp(g)[0]), _bits(ref_vjp(g)[0]))


def _relu_then_pool(params, x, mode):
    """The CNN's features as the CPU path and the parent's fused path
    compute them: both conv blocks with their ReLU, then
    ``_pool_flatten``."""
    from repro.models.cnn import _pool_flatten
    for w, b in ((params["conv1"], params["b1"]),
                 (params["conv2"], params["b2"])):
        x = kd.conv3x3_bias_relu(x, w, b, mode=mode)
    return _pool_flatten(x)


@pytest.mark.kernel_oracle
@pytest.mark.parametrize("case", sorted(POOL_CASES))
def test_fused_features_pool_before_relu_match_relu_then_pool(case):
    """``_features_fused`` (interpret: block 2 pools its pre-activation,
    then the ReLU) and its VJP with respect to the images and the four
    conv parameters: equal in value to ReLU-then-pool on the same
    convolution, and within the conv oracles' tolerances of the ``xla``
    branch (the im2col conv).  The coarse cases round images and weights
    to integers, on which both convolutions are exact, so the pool sees
    positive ties and all-zero windows."""
    from repro.models.cnn import _features_fused

    shape, coarse = POOL_CASES[case]
    lead = shape[:-3]
    ks = jax.random.split(jax.random.key(13), 6)
    grid = jnp.round if coarse else (lambda a: a)
    dev = lead[:-1]
    params = {"conv1": grid(jax.random.normal(ks[0], dev + (3, 3, 1, 4))),
              "b1": grid(jax.random.normal(ks[1], dev + (4,))),
              "conv2": grid(jax.random.normal(ks[2], dev + (3, 3, 4, 6))
                            * 0.3),
              "b2": grid(jax.random.normal(ks[3], dev + (6,)))}
    x = grid(jax.random.normal(ks[4], lead + (8, 8, 1)))

    def batched(fn):
        # leading dims beyond the batch: one model each, as the engine's
        # vmap over devices runs the step
        for _ in lead[1:]:
            fn = jax.vmap(fn)
        return fn

    def run(fn):
        return jax.vjp(batched(fn), params, x)

    if coarse:
        y1 = batched(lambda p, v: kd.conv3x3_bias_relu(
            v, p["conv1"], p["b1"], mode="xla"))(params, x)
        _assert_ties(batched(lambda p, v: kd.conv3x3_bias_relu(
            v, p["conv2"], p["b2"], mode="xla", relu=False))(params, y1))
    out, vjp = run(lambda p, v: _features_fused(p, v, "interpret"))
    same, same_vjp = run(lambda p, v: _relu_then_pool(p, v, "interpret"))
    cpu, cpu_vjp = run(lambda p, v: _relu_then_pool(p, v, "xla"))
    g = jax.random.normal(ks[5], out.shape)
    got, ref, ref_cpu = vjp(g), same_vjp(g), cpu_vjp(g)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(same))
    np.testing.assert_allclose(np.asarray(out), np.asarray(cpu), atol=1e-5)
    for a, b, c in zip(jax.tree.leaves(got), jax.tree.leaves(ref),
                       jax.tree.leaves(ref_cpu)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=1e-4)


@pytest.mark.kernel_oracle
@pytest.mark.parametrize("mode", ["xla", "interpret"])
@pytest.mark.parametrize("chunk", [16, 100, 250])
def test_eval_accuracy_in_chunks_equals_whole_set(mode, chunk, monkeypatch):
    """The engine's chunked test-set eval (tail chunk padded with label
    -1) gives bitwise the accuracy of one whole-set pass."""
    from repro.fl import engine
    from repro.models import cnn_accuracy_fast, cnn_specs, init_from_specs

    monkeypatch.setattr(engine, "EVAL_CHUNK", chunk)
    ks = jax.random.split(jax.random.key(8), 3)
    params = init_from_specs(cnn_specs(image_hw=8, c1=4, c2=8), ks[0])
    x = jax.random.normal(ks[1], (100, 8, 8, 1))
    y = jax.random.randint(ks[2], (100,), 0, 10)
    got = engine.eval_accuracy(params, x, y, mode)
    assert float(got) == float(cnn_accuracy_fast(params, x, y, mode))


@pytest.mark.kernel_oracle
def test_conv3x3_bf16_storage():
    """bf16 operands: f32 accumulation and bias, output cast back to bf16
    — matching the reference's f32-accumulate-then-cast within bf16
    rounding."""
    ks = jax.random.split(jax.random.key(2), 3)
    x = jax.random.normal(ks[0], (2, 8, 8, 4), jnp.bfloat16)
    w = (jax.random.normal(ks[1], (3, 3, 4, 6)) * 0.3).astype(jnp.bfloat16)
    b = (jax.random.normal(ks[2], (6,)) * 0.3).astype(jnp.bfloat16)
    got = _conv_block(x, w, b)
    ref = conv3x3_bias_relu_ref(x, w, b)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), atol=1e-2)


@pytest.mark.kernel_oracle
@pytest.mark.parametrize("m", [1, 100, 256, 257, 400])
def test_eval_head_matches_ref_on_tile_tails(m):
    """Exact correct-count equality across M-tile tails (the count is an
    integer sum of per-tile integer partials — no tolerance)."""
    ks = jax.random.split(jax.random.key(3), 4)
    f, c = 33, 10
    feats = jax.random.normal(ks[0], (m, f))
    wmat = jax.random.normal(ks[1], (f, c)) * 0.1
    bias = jax.random.normal(ks[2], (c,)) * 0.1
    labels = jax.random.randint(ks[3], (m,), 0, c)
    got = eval_head(feats, wmat, bias, labels, interpret=True)
    ref = eval_head_ref(feats, wmat, bias, labels)
    assert got.dtype == jnp.int32
    assert int(got) == int(ref)


@pytest.mark.kernel_oracle
def test_eval_head_bf16_inputs():
    """bf16 feats/weights: both paths cast to f32 before the identical
    matmul, so the argmax — and the count — must agree exactly."""
    ks = jax.random.split(jax.random.key(4), 4)
    m, f, c = 70, 21, 5
    feats = jax.random.normal(ks[0], (m, f), jnp.bfloat16)
    wmat = (jax.random.normal(ks[1], (f, c)) * 0.2).astype(jnp.bfloat16)
    bias = (jax.random.normal(ks[2], (c,)) * 0.2).astype(jnp.bfloat16)
    labels = jax.random.randint(ks[3], (m,), 0, c)
    got = eval_head(feats, wmat, bias, labels, interpret=True)
    ref = eval_head_ref(feats, wmat, bias, labels)
    assert int(got) == int(ref)


@pytest.mark.kernel_oracle
@settings(max_examples=10, deadline=None)
@given(n=st.integers(1, 8),
       l=st.sampled_from([1, 37, CTILE - 1, CTILE, CTILE + 5]),
       seed=st.integers(0, 99))
def test_coef_agg_matches_ref_on_tile_tails(n, l, seed):
    ks = jax.random.split(jax.random.key(seed), 4)
    w = jax.random.normal(ks[0], (n, l))
    aux = jax.random.normal(ks[1], (n, l))
    coef = jax.nn.softmax(jax.random.normal(ks[2], (n,)))
    msk = (jax.random.uniform(ks[3], (n,)) > 0.4).astype(jnp.float32)
    got = coef_agg(w, coef, interpret=True)
    ref = coef_agg_ref(w, coef)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-6)
    got_p = coef_agg_pair(w, aux, coef * msk, coef * (1.0 - msk),
                          interpret=True)
    ref_p = coef_agg_pair_ref(w, aux, coef * msk, coef * (1.0 - msk))
    np.testing.assert_allclose(np.asarray(got_p), np.asarray(ref_p),
                               atol=1e-6)


@pytest.mark.kernel_oracle
def test_coef_agg_bf16_storage_promotes_to_f32():
    """bf16 stacked weights with f32 coefficients: the aggregate is f32 on
    both paths (XLA's promotion rule), values within exact f32 math of the
    bf16 inputs."""
    w = jax.random.normal(jax.random.key(5), (4, 1000), jnp.bfloat16)
    coef = jnp.asarray([0.4, 0.3, 0.2, 0.1])
    got = coef_agg(w, coef, interpret=True)
    ref = coef_agg_ref(w, coef)
    assert got.dtype == ref.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-6)


@pytest.mark.kernel_oracle
def test_coef_agg_zero_coef_slots_are_exact_noops():
    """The padded-slot contract: a zero-coefficient row contributes exactly
    nothing, bitwise, whatever garbage it carries (0 * x == 0 in f32 for
    finite x)."""
    w_live = jax.random.normal(jax.random.key(6), (3, 500))
    garbage = jnp.full((2, 500), 1e6)
    w_pad = jnp.concatenate([w_live, garbage])
    w_zero = jnp.concatenate([w_live, jnp.zeros((2, 500))])
    coef = jnp.asarray([0.5, 0.3, 0.2, 0.0, 0.0])
    a = coef_agg(w_pad, coef, interpret=True)
    b = coef_agg(w_zero, coef, interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------- dispatch entry parity
@pytest.mark.kernel_oracle
def test_dispatch_cold_aggregates_match_hieavg_references():
    """The cold-boot dispatch entries (generalized coefficient aggregate)
    against ``core.hieavg`` — including an all-invalid edge, which must
    aggregate to exact zeros on both paths, and padded garbage slots."""
    ks = jax.random.split(jax.random.key(7), 2)
    w = {"a": jax.random.normal(ks[0], (3, 4, 5, 3)),
         "b": jax.random.normal(ks[1], (3, 4, 17))}
    valid = jnp.asarray([[1, 1, 1, 0], [0, 0, 0, 0], [1, 1, 1, 1]], bool)
    got = kd.edge_aggregate_cold_batched(w, valid, mode="interpret")
    ref = hieavg.edge_aggregate_cold_batched(w, valid)
    for k in w:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(ref[k]),
                                   atol=1e-6)
    wg = {"p": jax.random.normal(jax.random.key(8), (3, 7, 2))}
    j_arr = jnp.asarray([3.0, 2.0, 4.0])
    got_g = kd.global_aggregate_cold(wg, j_arr, mode="interpret")
    ref_g = hieavg.global_aggregate_cold(wg, j_arr)
    np.testing.assert_allclose(np.asarray(got_g["p"]),
                               np.asarray(ref_g["p"]), atol=1e-6)


@pytest.mark.kernel_oracle
def test_dispatch_baseline_aggregates_match_references():
    """``kd.fedavg`` / ``kd.delayed_grad`` against ``core.baselines`` —
    same coefficients, same staleness discount, same store updates."""
    ks = jax.random.split(jax.random.key(9), 3)
    w = {"p": jax.random.normal(ks[0], (5, 11, 3)),
         "q": jax.random.normal(ks[1], (5, 40))}
    pw = jnp.asarray([2.0, 1.0, 3.0, 0.0, 0.0])   # padded slots: zero weight
    got = kd.fedavg(w, pw, mode="interpret")
    ref = baselines.fedavg(w, pw)
    for k in w:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(ref[k]),
                                   atol=1e-6)

    pending = jax.tree.map(lambda x: x * 0.9 + 0.05, w)
    mask = jnp.asarray([True, False, True, False, True])
    age = jnp.asarray([0.0, 1.0, 0.0, 4.0, 2.0])
    beta, delta = jnp.float32(0.5), jnp.float32(3.0)
    a_got, p_got, age_got = kd.delayed_grad(w, mask, pending, age, beta,
                                            delta, pw, mode="interpret")
    a_ref, p_ref, age_ref = baselines.delayed_grad(w, mask, pending, age,
                                                   beta, delta, pw)
    for k in w:
        np.testing.assert_allclose(np.asarray(a_got[k]),
                                   np.asarray(a_ref[k]), atol=1e-6)
        np.testing.assert_array_equal(np.asarray(p_got[k]),
                                      np.asarray(p_ref[k]))
    np.testing.assert_array_equal(np.asarray(age_got), np.asarray(age_ref))


@pytest.mark.kernel_oracle
def test_dispatch_conv_eval_interpret_matches_xla_branch():
    """The two train/eval dispatch entries: interpret (XLA's convolution,
    the eval-head kernel) vs the xla branch (the engine's original
    im2col conv and eval chain, bit-for-bit)."""
    ks = jax.random.split(jax.random.key(10), 3)
    x = jax.random.normal(ks[0], (2, 8, 8, 3))
    w = jax.random.normal(ks[1], (3, 3, 3, 6)) * 0.3
    b = jax.random.normal(ks[2], (6,)) * 0.3
    np.testing.assert_allclose(
        np.asarray(kd.conv3x3_bias_relu(x, w, b, mode="interpret")),
        np.asarray(kd.conv3x3_bias_relu(x, w, b, mode="xla")), atol=1e-5)

    ks = jax.random.split(jax.random.key(11), 4)
    feats = jax.random.normal(ks[0], (50, 20))
    wmat = jax.random.normal(ks[1], (20, 10)) * 0.1
    bias = jax.random.normal(ks[2], (10,)) * 0.1
    labels = jax.random.randint(ks[3], (50,), 0, 10)
    assert int(kd.eval_head(feats, wmat, bias, labels, mode="interpret")) \
        == int(kd.eval_head(feats, wmat, bias, labels, mode="xla"))


# ------------------------------------------------------------ engine parity
def test_engine_kernel_plane_matches_xla_standalone():
    """The acceptance pin: fused-kernel engine == pure-XLA engine on a
    standalone run (same inputs, same trajectories)."""
    a = _sim(kernel_mode="xla").run()
    b = _sim(kernel_mode="interpret").run()
    _close(a, b)
    np.testing.assert_allclose(b.sim_clock, a.sim_clock, rtol=1e-6)


def test_engine_kernel_plane_bf16_history():
    a = _sim(kernel_mode="xla", history_dtype=jnp.bfloat16).run()
    b = _sim(kernel_mode="interpret", history_dtype=jnp.bfloat16).run()
    _close(a, b, acc_atol=0.02)
    np.testing.assert_allclose(b.loss, a.loss, rtol=1e-3, atol=1e-4)


def test_auto_mode_on_cpu_is_bitwise_the_xla_engine():
    """On CPU the default must add literally nothing: "auto" and "xla"
    resolve to the same jit cache entry and the same numbers."""
    a = _sim(kernel_mode="auto").run()
    b = _sim(kernel_mode="xla").run()
    np.testing.assert_array_equal(a.accuracy, b.accuracy)
    np.testing.assert_array_equal(a.loss, b.loss)


def test_sweep_kernel_plane_parity_multibucket():
    """The acceptance pin, sweep edition: a padded multi-bucket
    shape-changing grid through the fused kernels == the pure-XLA grid
    per point, including padded points and the simulated clock."""
    ovs = [{"n_edges": 2}, {"k_edge_rounds": 1}, {"t_global_rounds": 2},
           {}]
    plan_x = plan_sweep(TINY, overrides=ovs, kernel_mode="xla",
                        max_buckets=2, bucket_waste=1.0, **KW)
    plan_i = plan_sweep(TINY, overrides=ovs, kernel_mode="interpret",
                        max_buckets=2, bucket_waste=1.0, **KW)
    assert plan_x.kernel_mode == "xla"
    assert plan_i.kernel_mode == "interpret"
    assert len(plan_i.buckets) == 2
    sx, si = run_plan(plan_x), run_plan(plan_i)
    _close(sx, si)
    np.testing.assert_allclose(si.sim_clock, sx.sim_clock, rtol=1e-5)
    # ...and against standalone engine runs that never saw the fabric
    for p, (ov, seed) in enumerate(si.points):
        s = dataclasses.replace(TINY, **ov)
        r = BHFLSimulator(s, "hieavg", "temporary", "temporary", seed=seed,
                          kernel_mode="xla", **KW).run()
        tv = int(si.t_valid[p])
        np.testing.assert_allclose(si.accuracy[p, :tv], r.accuracy,
                                   atol=1e-6)
        np.testing.assert_allclose(si.loss[p, :tv], r.loss, rtol=1e-5,
                                   atol=1e-6)


def test_sweep_mixed_aggregation_kernel_plane_parity():
    """The acceptance pin, mixed-aggregation edition: hieavg, delayed_grad
    and fedavg points compile as ONE traced-"switched" program across a
    bucketed shape-changing grid, and the fused kernels must reproduce
    the pure-XLA grid per point — every aggregation dispatch entry
    (warm, cold, fedavg, delayed-grad) exercised inside one scan.
    ``bucket_cost="proxy"`` on both plans so the grids bucket identically
    and the comparison is point-for-point by construction."""
    ovs = [{"aggregation": "fedavg"}, {"aggregation": "delayed_grad"},
           {"n_edges": 2}, {}]
    kwb = dict(overrides=ovs, max_buckets=2, bucket_waste=1.0,
               bucket_cost="proxy", **KW)
    plan_x = plan_sweep(TINY, kernel_mode="xla", **kwb)
    plan_i = plan_sweep(TINY, kernel_mode="interpret", **kwb)
    assert plan_x.aggregator == plan_i.aggregator == "switched"
    sx, si = run_plan(plan_x), run_plan(plan_i)
    _close(sx, si)
    np.testing.assert_allclose(si.sim_clock, sx.sim_clock, rtol=1e-5)


# ---------------------------------------------------------------- donation
def test_donated_engine_matches_non_donated():
    """Donation smoke: same numbers, data plane never consumed."""
    inp_a = build_inputs(_sim())
    inp_b = build_inputs(_sim())
    a = run_engine(inp_a)
    b = run_engine_donated(inp_b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # the seed-major data plane is aliased by design and must survive
    assert not inp_b.train_x.is_deleted()
    assert not jax.tree.leaves(inp_b.init_w)[0].is_deleted()
    # the non-donated entry leaves everything reusable
    c = run_engine(inp_a)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(c[0]))


def test_split_inputs_partition():
    """Every EngineInputs field lands on exactly one side; the shared
    side is exactly the data plane (+ seed_idx when plan-wide)."""
    inp = build_inputs(_sim())
    hot, shared = split_inputs(inp)
    assert set(shared) == SHARED_DATA_FIELDS
    hot2, shared2 = split_inputs(inp, shared_seed_idx=True)
    assert set(shared2) == SHARED_DATA_FIELDS | {"seed_idx"}
    assert not (set(hot) & set(shared))
    assert set(hot) | set(shared) == set(hot2) | set(shared2)


def test_donated_plan_matches_and_is_consumed_once():
    ovs = [{"straggler_frac": 0.4}, {}]
    ref = run_sweep(TINY, overrides=ovs, **KW)        # fresh plan per call
    plan = plan_sweep(TINY, overrides=ovs, **KW)
    got = run_plan(plan)                              # donate=True default
    np.testing.assert_array_equal(got.accuracy, ref.accuracy)
    np.testing.assert_array_equal(got.loss, ref.loss)
    assert all(b.inputs is None for b in plan.buckets)
    with pytest.raises(ValueError, match="consumed"):
        run_plan(plan)
    with pytest.raises(ValueError, match="consumed"):
        plan.inputs          # the single-bucket accessor raises too
    # donate=False keeps a plan re-runnable, same numbers both times
    plan2 = plan_sweep(TINY, overrides=ovs, **KW)
    r1 = run_plan(plan2, donate=False)
    r2 = run_plan(plan2, donate=False)
    assert all(b.inputs is not None for b in plan2.buckets)
    np.testing.assert_array_equal(r1.accuracy, ref.accuracy)
    np.testing.assert_array_equal(r2.accuracy, ref.accuracy)
