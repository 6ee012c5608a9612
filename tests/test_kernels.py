"""Pallas kernel validation: interpret=True vs pure-jnp oracles,
hypothesis shape/dtype sweeps."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import hieavg
from repro.kernels.flash_attention import flash_attention, flash_attention_1h
from repro.kernels.hieavg_agg import fused_mix_and_update, hieavg_agg
from repro.kernels.ref import flash_attention_ref, hieavg_agg_ref
from repro.models.attention import _sdpa


# -------------------------------------------------------------- hieavg_agg
@settings(max_examples=12, deadline=None)
@given(n=st.integers(2, 9), l=st.sampled_from([64, 1000, 2048, 3000]),
       dt=st.sampled_from(["float32", "bfloat16"]), seed=st.integers(0, 99))
def test_hieavg_agg_matches_ref(n, l, dt, seed):
    dt = jnp.dtype(dt)
    ks = jax.random.split(jax.random.key(seed), 6)
    w = jax.random.normal(ks[0], (n, l), dt)
    prev = jax.random.normal(ks[1], (n, l), dt)
    dmean = jax.random.normal(ks[2], (n, l), dt) * 0.1
    mask = jax.random.bernoulli(ks[3], 0.7, (n,))
    cp = jax.random.uniform(ks[4], (n,))
    ce = jax.random.uniform(ks[5], (n,)) * 0.3
    nobs = jnp.arange(n, dtype=jnp.float32)
    ref = hieavg_agg_ref(w, prev, dmean, mask, cp, ce, nobs)
    got = hieavg_agg(w, prev, dmean, mask, cp, ce, nobs)
    tol = 1e-5 if dt == jnp.float32 else 6e-2
    for r, g in zip(ref, got):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(r, np.float32), atol=tol)


def test_fused_edge_aggregate_matches_core():
    """``fused_mix_and_update`` with uniform 1/n part weights == core
    ``hieavg.edge_aggregate`` end to end."""
    n = 5
    stacked = {"a": jax.random.normal(jax.random.key(0), (n, 13, 7)),
               "b": jax.random.normal(jax.random.key(1), (n, 40))}
    hist = hieavg.init_history(stacked)
    hist = dataclasses.replace(
        hist,
        delta_mean=jax.tree.map(lambda x: x * 0.05, stacked),
        n_obs=jnp.full((n,), 3.0),
        miss_count=jnp.array([0.0, 1.0, 0.0, 2.0, 0.0]))
    mask = jnp.array([True, False, True, False, True])
    for normalize in (False, True):
        agg_ref, h_ref = hieavg.edge_aggregate(stacked, mask, hist,
                                               normalize=normalize)
        agg_got, h_got = fused_mix_and_update(
            stacked, mask, hist, jnp.full((n,), 1.0 / n), 0.9, 0.9,
            normalize)
        for k in stacked:
            np.testing.assert_allclose(np.asarray(agg_got[k]),
                                       np.asarray(agg_ref[k]), atol=1e-5)
            np.testing.assert_allclose(np.asarray(h_got.prev_w[k]),
                                       np.asarray(h_ref.prev_w[k]),
                                       atol=1e-5)
            np.testing.assert_allclose(np.asarray(h_got.delta_mean[k]),
                                       np.asarray(h_ref.delta_mean[k]),
                                       atol=1e-5)
        np.testing.assert_array_equal(np.asarray(h_got.n_obs),
                                      np.asarray(h_ref.n_obs))
        np.testing.assert_array_equal(np.asarray(h_got.miss_count),
                                      np.asarray(h_ref.miss_count))


# ----------------------------------------------------------------- flash
@settings(max_examples=10, deadline=None)
@given(sq=st.sampled_from([1, 128, 300, 512]),
       skv=st.sampled_from([256, 300, 512]),
       d=st.sampled_from([64, 80, 128]),
       causal=st.booleans(), seed=st.integers(0, 50))
def test_flash_1h_matches_ref(sq, skv, d, causal, seed):
    if causal and sq > skv:
        sq = skv
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (sq, d), jnp.float32)
    k = jax.random.normal(ks[1], (skv, d), jnp.float32)
    v = jax.random.normal(ks[2], (skv, d), jnp.float32)
    off = skv - sq if causal else 0
    ref = flash_attention_ref(q, k, v, causal=causal, q_offset=off)
    got = flash_attention_1h(q, k, v, causal=causal, q_offset=off)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)


@settings(max_examples=6, deadline=None)
@given(window=st.sampled_from([32, 100, 256]), seed=st.integers(0, 20))
def test_flash_1h_sliding_window(window, seed):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (512, 64), jnp.float32)
    k = jax.random.normal(ks[1], (512, 64), jnp.float32)
    v = jax.random.normal(ks[2], (512, 64), jnp.float32)
    ref = flash_attention_ref(q, k, v, causal=True, window=window)
    got = flash_attention_1h(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)


def test_flash_gqa_matches_sdpa():
    q = jax.random.normal(jax.random.key(0), (2, 384, 8, 64))
    k = jax.random.normal(jax.random.key(1), (2, 384, 2, 64))
    v = jax.random.normal(jax.random.key(2), (2, 384, 2, 64))
    ref = _sdpa(q, k, v, causal=True, window=None)
    got = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)


def test_flash_bf16():
    q = jax.random.normal(jax.random.key(0), (1, 256, 4, 64), jnp.bfloat16)
    k = jax.random.normal(jax.random.key(1), (1, 256, 4, 64), jnp.bfloat16)
    v = jax.random.normal(jax.random.key(2), (1, 256, 4, 64), jnp.bfloat16)
    ref = _sdpa(q, k, v, causal=True, window=None)
    got = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2)


def test_flash_backend_switch_matches_xla_path():
    """models/attention with USE_FLASH_KERNEL routes through the Pallas
    kernel and must reproduce the XLA chunked path end to end."""
    import repro.models.attention as att
    from repro.configs import get_smoke
    from repro.models import forward_train, init_from_specs, param_specs

    cfg = get_smoke("h2o-danube-1.8b")
    params = init_from_specs(param_specs(cfg), jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (1, 32), 0, cfg.vocab)
    ref, _ = forward_train(params, toks, cfg)
    att.USE_FLASH_KERNEL = True
    try:
        got, _ = forward_train(params, toks, cfg)
    finally:
        att.USE_FLASH_KERNEL = False
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=3e-4)
