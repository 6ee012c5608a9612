"""The paper's MNIST CNN (Sec. 6.1.5): two conv layers, one max-pool, one
flatten, one dense layer.  Used by the BHFL simulator and Fig. 2-6 repros."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .spec import ParamSpec


def cnn_specs(image_hw: int = 28, channels: int = 1, n_classes: int = 10,
              c1: int = 32, c2: int = 64) -> dict:
    pooled = image_hw // 2  # one 2x2 max-pool after the convs (SAME padding)
    flat = pooled * pooled * c2
    return {
        "conv1": ParamSpec((3, 3, channels, c1), (None, None, None, None)),
        "b1": ParamSpec((c1,), (None,), init="zeros"),
        "conv2": ParamSpec((3, 3, c1, c2), (None, None, None, None)),
        "b2": ParamSpec((c2,), (None,), init="zeros"),
        "dense": ParamSpec((flat, n_classes), (None, None)),
        "b3": ParamSpec((n_classes,), (None,), init="zeros"),
    }


def _conv3x3_same(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """3x3 SAME conv via im2col + einsum.

    Pure dot-products instead of lax.conv: XLA:CPU's batched conv gradients
    (batch_group_count under vmap) are orders of magnitude slower than the
    equivalent matmul, and the FL simulator vmaps over dozens of devices,
    each with its own weights.  The vmapped gradient at 25 devices x 32 x
    28x28x32, on a Xeon host: 19.6 s for lax.conv against 0.94 s for the
    im2col einsum with per-device weights; with weights shared by all
    devices lax.conv wins, 0.53 s against 1.21 s.  A TPU runs the
    per-device lax.conv well: ``kernels.dispatch`` runs it there.
    x: [..., H, W, Cin]; w: [3, 3, Cin, Cout].
    """
    h, wd = x.shape[-3], x.shape[-2]
    pad = [(0, 0)] * (x.ndim - 3) + [(1, 1), (1, 1), (0, 0)]
    xp = jnp.pad(x, pad)
    # sum of 9 shifted matmuls — no 9x im2col memory blowup
    out = None
    for i in range(3):
        for j in range(3):
            term = jnp.einsum("...c,co->...o",
                              xp[..., i:i + h, j:j + wd, :], w[i, j])
            out = term if out is None else out + term
    return out


_TAPS = tuple((i, j) for i in range(3) for j in range(3))


@jax.custom_vjp
def im2col3x3(x: jnp.ndarray) -> jnp.ndarray:
    """SAME-padded 3x3 patches: [..., H, W, Cin] -> [..., H, W, 9·Cin].

    Patch channels are (i, j, c)-ordered, matching ``w.reshape(9·Cin,
    Cout)``.  The backward is col2im written out as nine shifted adds.
    XLA's own transpose of the concatenate instead splits the cotangent
    into a ``[..., Cin, 9]`` buffer whose 9-wide minor dim a TPU pads to
    128 lanes: at the paper's widths that one buffer is over 10 GB for a
    3-point sweep.
    """
    h, wd = x.shape[-3], x.shape[-2]
    pad = [(0, 0)] * (x.ndim - 3) + [(1, 1), (1, 1), (0, 0)]
    xp = jnp.pad(x, pad)
    return jnp.concatenate([xp[..., i:i + h, j:j + wd, :]
                            for i, j in _TAPS], axis=-1)


def _im2col_fwd(x):
    return im2col3x3(x), None


def _im2col_bwd(_, dcols):
    h, wd, c = dcols.shape[-3], dcols.shape[-2], dcols.shape[-1] // 9
    dxp = jnp.zeros(dcols.shape[:-3] + (h + 2, wd + 2, c), dcols.dtype)
    for k, (i, j) in enumerate(_TAPS):
        dxp = dxp.at[..., i:i + h, j:j + wd, :].add(
            dcols[..., k * c:(k + 1) * c])
    return (dxp[..., 1:h + 1, 1:wd + 1, :],)


im2col3x3.defvjp(_im2col_fwd, _im2col_bwd)


def _conv3x3_same_im2col(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """3x3 SAME conv as ONE batched matmul (im2col).

    Costs 9x activation memory vs the shifted-sum form but issues a single
    large dot the backend can block efficiently — ~1.6x faster end-to-end on
    the vmapped FL training step at the paper's model sizes (EXPERIMENTS.md
    §Perf).  Same math as ``_conv3x3_same`` up to summation order; the
    batched engine trains with this form, the legacy reference loop keeps
    the shifted sum.  x: [..., H, W, Cin]; w: [3, 3, Cin, Cout].
    """
    return jnp.einsum("...k,ko->...o", im2col3x3(x),
                      w.reshape(-1, w.shape[-1]))


def _pool_flatten(x: jnp.ndarray) -> jnp.ndarray:
    # 2x2 stride-2 max-pool via reshape — identical to reduce_window but its
    # gradient avoids SelectAndScatter, which is pathologically slow on CPU.
    # The CPU path's pool, and the oracle of the fused path's
    # ``max_pool2x2`` (tests/test_kernel_plane.py).
    b, h, w_, c = x.shape
    x = x.reshape(b, h // 2, 2, w_ // 2, 2, c).max(axis=(2, 4))
    return x.reshape(x.shape[0], -1)


def _windows(x: jnp.ndarray) -> jnp.ndarray:
    """[..., H, W, C] viewed as [..., H/2, 2, W/2, 2, C]: a bitcast in
    the layout XLA picks, whose axes -4 and -2 span each 2x2 window."""
    h, w_, c = x.shape[-3:]
    return x.reshape(x.shape[:-3] + (h // 2, 2, w_ // 2, 2, c))


def max_pool2x2(x: jnp.ndarray) -> jnp.ndarray:
    """2x2 stride-2 max-pool: [..., H, W, C] -> [..., H/2, W/2, C].

    The same function as ``_pool_flatten``'s pool, gradient included, to
    the bit: a tie splits the window's gradient evenly, ``dx = (x ==
    max) · g / count`` with ``count`` the taps equal to the max, the
    rule of ``reduce_max``'s own derivative (``lax.reduce_window``'s
    gradient would give a tie's whole gradient to one tap instead).

    Its residual is a 4-bit mask per output, bit ``2i + j`` set where tap
    ``(i, j)`` equals the max.  The forward finds max and mask in one
    variadic reduction, one read of ``x``; the backward reads only the
    pooled gradient and the mask, counts the set bits elementwise, and
    writes ``dx``.  ``reduce_max``'s own derivative instead reads ``x``
    twice more at full size: once to count the ties, as a reduction over
    the window axes, and once to place the gradient.

    A device trace files the backward's ops under this function's frame
    and the forward's under ``_max_pool_fwd``: JAX gives the ops of a
    ``custom_vjp`` backward the frame of its call site.
    """
    return _max_pool2x2(x)


@jax.custom_vjp
def _max_pool2x2(x):
    return _windows(x).max(axis=(-4, -2))


def _max_pool_fwd(x):
    v = _windows(x)
    nd = v.ndim
    tap = 2 * lax.broadcasted_iota(jnp.uint8, v.shape, nd - 4) \
        + lax.broadcasted_iota(jnp.uint8, v.shape, nd - 2)

    def pick(a, b):
        (ma, ka), (mb, kb) = a, b
        k = jnp.where(ma == mb, ka | kb, jnp.where(ma > mb, ka, kb))
        return jnp.maximum(ma, mb), k

    return lax.reduce((v, jnp.left_shift(jnp.uint8(1), tap)),
                      (jnp.array(-jnp.inf, x.dtype), jnp.uint8(0)),
                      pick, (nd - 4, nd - 2))


def _max_pool_bwd(mask, g):
    bits = [((mask >> k) & 1).astype(g.dtype) for k in range(4)]
    share = g / ((bits[0] + bits[1]) + (bits[2] + bits[3]))
    d = [bit * share for bit in bits]
    # stacked, not broadcast over the window: for the conv backward that
    # consumes dx, the TPU compiler writes a broadcast out at full size
    rows = [jnp.stack(d[2 * i:2 * i + 2], axis=-2) for i in range(2)]
    dx = jnp.stack(rows, axis=-4)       # [..., H/2, 2, W/2, 2, C]
    h2, w2, c = g.shape[-3:]
    return (dx.reshape(g.shape[:-3] + (2 * h2, 2 * w2, c)),)


_max_pool2x2.defvjp(_max_pool_fwd, _max_pool_bwd)


def _apply(params: dict, images: jnp.ndarray, conv) -> jnp.ndarray:
    x = images
    for w, b in ((params["conv1"], params["b1"]),
                 (params["conv2"], params["b2"])):
        x = jax.nn.relu(conv(x, w) + b)
    x = _pool_flatten(x)
    return x @ params["dense"] + params["b3"]


def _features_fused(params: dict, images: jnp.ndarray, kernel_mode: str
                    ) -> jnp.ndarray:
    """Pooled/flattened features with the conv blocks routed through
    ``kernels.dispatch.conv3x3_bias_relu`` (XLA's own convolution, fused
    with its bias and ReLU).

    Block 2 pools its pre-activation and applies the ReLU to the pooled
    tensor: the same values as ``_pool_flatten(relu(...))``, gradients
    included (a zero may change sign), since the ReLU is monotone and
    its derivative is 0 at and below 0, with one
    full-size pass each way (``max_pool2x2``) in place of the ReLU
    output, its mask and the window reductions."""
    from repro.kernels import dispatch as _kd
    x = _kd.conv3x3_bias_relu(images, params["conv1"], params["b1"],
                              mode=kernel_mode)
    x = _kd.conv3x3_bias_relu(x, params["conv2"], params["b2"],
                              mode=kernel_mode, relu=False)
    x = jax.nn.relu(max_pool2x2(x))
    return x.reshape(x.shape[:-3] + (-1,))


def cnn_apply(params: dict, images: jnp.ndarray) -> jnp.ndarray:
    """images [B, H, W, C] -> logits [B, n_classes]."""
    return _apply(params, images, _conv3x3_same)


def cnn_apply_fast(params: dict, images: jnp.ndarray,
                   kernel_mode: str = "xla") -> jnp.ndarray:
    """``cnn_apply`` with the im2col conv — the engine's training path.

    ``kernel_mode`` (resolved or ``"auto"``) routes the conv blocks:
    ``"xla"`` (the default, bit-identical to what this function always
    did) keeps the plain im2col einsum; the fused modes (the TPU's) run
    them as XLA's own convolution, through ``kernels.dispatch``.  The
    engine threads its resolved mode here.
    """
    from repro.kernels import dispatch as _kd
    mode = _kd.resolve_kernel_mode(kernel_mode)
    if mode == "xla":
        return _apply(params, images, _conv3x3_same_im2col)
    feats = _features_fused(params, images, mode)
    return feats @ params["dense"] + params["b3"]


def _loss(apply, params, images, labels):
    logits = apply(params, images)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def cnn_loss(params: dict, images: jnp.ndarray, labels: jnp.ndarray
             ) -> jnp.ndarray:
    return _loss(cnn_apply, params, images, labels)


def cnn_loss_fast(params: dict, images: jnp.ndarray, labels: jnp.ndarray,
                  kernel_mode: str = "xla") -> jnp.ndarray:
    def apply(p, im):
        return cnn_apply_fast(p, im, kernel_mode=kernel_mode)
    return _loss(apply, params, images, labels)


def _accuracy(apply, params, images, labels):
    return jnp.mean((jnp.argmax(apply(params, images), -1) == labels)
                    .astype(jnp.float32))


def cnn_accuracy(params: dict, images: jnp.ndarray, labels: jnp.ndarray
                 ) -> jnp.ndarray:
    return _accuracy(cnn_apply, params, images, labels)


def cnn_correct_fast(params: dict, images: jnp.ndarray, labels: jnp.ndarray,
                     kernel_mode: str = "xla") -> jnp.ndarray:
    """Int32 count of correct predictions on the ``cnn_apply_fast``
    forward (the engine's eval path); a label of -1 never counts.

    Under a fused ``kernel_mode`` the whole eval runs kernel-routed: conv
    blocks as XLA's own convolution, then the classifier head as one
    logits → argmax → correct-count pass (``kernels.dispatch.eval_head``)
    — the logits buffer never materializes.
    """
    from repro.kernels import dispatch as _kd
    mode = _kd.resolve_kernel_mode(kernel_mode)
    if mode == "xla":
        pred = jnp.argmax(cnn_apply_fast(params, images), -1)
        return jnp.sum((pred == labels).astype(jnp.int32))
    feats = _features_fused(params, images, mode)
    return _kd.eval_head(feats, params["dense"], params["b3"], labels,
                         mode=mode)


def cnn_accuracy_fast(params: dict, images: jnp.ndarray, labels: jnp.ndarray,
                      kernel_mode: str = "xla") -> jnp.ndarray:
    """``cnn_accuracy`` through ``cnn_correct_fast``: count / #rows, the
    same f32 value as a mean of hits (both exact)."""
    count = cnn_correct_fast(params, images, labels, kernel_mode)
    return count.astype(jnp.float32) / labels.shape[0]
