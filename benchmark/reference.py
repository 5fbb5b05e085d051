"""The plain reference's arithmetic: straightforward ``jax.numpy`` in
float32 with every matrix product at ``highest`` precision, no kernels,
no space-to-depth, no sharding rules. It imports nothing of the program.

``Ops(mode)`` are the layer functions a configuration's reference
(``benchmark/references/<name>.py``) is written in. ``mode`` is
``f32`` for the reference itself and ``fp8`` for the control: the same
arithmetic with each convolution's three products in 8-bit floats, the
nearest precision below the bf16 the configurations state (input and
weights rounded to e4m3, the incoming gradient to e5m2, each scaled to
the tensor's largest value; the sums stay in float32, as the program's
bf16 products sum in float32).

A whole batch at float32 and 640x960 does not fit a 16 GB chip (the
chip's compiler, asked in the sandbox: 23.3 GB for 16 rows of the course
UNet), so the reference goes through the batch in BLOCKS OF ROWS. The
loss is not a sum over rows: its Dice term is a ratio of sums over the
whole batch. So a step makes two passes, and is exact:

1. forward only, block by block: the loss's sufficient statistics
   (``loss_stats``: the sum of the BCE terms, of p*t, of p, of t, and the
   count), added up over the blocks; the loss is ``loss_from_stats`` of
   the total, and ``c`` its gradient for the five statistics;
2. forward and backward, block by block: the gradient of ``c . stats`` of
   the block, added up over the blocks: the chain rule, nothing left out.

A configuration whose layers take statistics over the batch (BatchNorm)
cannot be cut so: its reference module says ``stateful``, and what
follows it in blocks between its BatchNorms (a module ``reference_bn``
with ``Blocks(ref_module, config, mode, put)``) comes as a new file with
the first such configuration; none has a cell yet.

``follow(...)`` drives the reference through the first steps of training
as the configuration states them: BCE minus log soft-Dice over the whole
batch, the gradient scaled by the batch size (the upstream
``(batch_size * loss).backward()``), Adam with L2 weight decay folded
into the gradient, bias-corrected, lr from the configuration.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
_DIMS = ("NHWC", "HWIO", "NHWC")


def _fp8(x, dtype, top):
    """Round to an 8-bit float at a per-tensor scale (the tensor's largest
    value goes to ``top``, the type's largest)."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, top / amax, 1.0)
    return (x * scale).astype(dtype).astype(x.dtype) / scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fp8_product(fn, x, w):
    """``fn(x, w)`` (a convolution) as an 8-bit float recipe computes it:
    both operands rounded to e4m3 in the forward pass, and in the backward
    pass the incoming gradient rounded to e5m2 before both of its products
    (the usual split: more mantissa forward, more range backward)."""
    return fn(_fp8(x, jnp.float8_e4m3fn, 448.0), _fp8(w, jnp.float8_e4m3fn, 448.0))


def _fp8_product_fwd(fn, x, w):
    xq = _fp8(x, jnp.float8_e4m3fn, 448.0)
    wq = _fp8(w, jnp.float8_e4m3fn, 448.0)
    return fn(xq, wq), (xq, wq)


def _fp8_product_bwd(fn, saved, g):
    return jax.vjp(fn, *saved)[1](_fp8(g, jnp.float8_e5m2, 57344.0))


_fp8_product.defvjp(_fp8_product_fwd, _fp8_product_bwd)


def sub(params: dict, prefix: str) -> dict:
    """The leaves of one block: those named ``<prefix>/...``."""
    return {k: v for k, v in params.items() if k.startswith(prefix + "/")}


def _conv_same(x, w):
    return lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=_DIMS, precision=HIGHEST)


def _upconv(x, w):
    k = w.shape[0]
    n, h, wd, _ = x.shape
    y = jnp.einsum("nhwc,abco->nhawbo", x, w, precision=HIGHEST)
    return y.reshape(n, h * k, wd * k, w.shape[-1])


class Ops:
    def __init__(self, mode: str = "f32", remat: bool = True):
        if mode not in ("f32", "fp8"):
            raise ValueError(f"unknown reference mode {mode!r}")
        self.mode = mode
        self.remat = remat

    def unit(self, fn):
        """A block of the network whose inner activations are recomputed
        in the backward pass, so that a block of rows at float32 fits."""
        return jax.checkpoint(fn) if self.remat else fn

    def _product(self, fn, x, w):
        return _fp8_product(fn, x, w) if self.mode == "fp8" else fn(x, w)

    def conv(self, x, w, b):
        y = self._product(_conv_same, x, w)
        return y if b is None else y + b

    def upconv(self, x, w, b):
        """k x k, stride k transposed convolution: output pixel
        (k*i + di, k*j + dj) is input pixel (i, j) through tap (di, dj)."""
        y = self._product(_upconv, x, w)
        return y if b is None else y + b

    @staticmethod
    def relu(x):
        return jnp.maximum(x, 0.0)

    @staticmethod
    def maxpool(x):
        """2x2, stride 2. ``reduce_window``'s gradient chooses the winner
        from the operand it is given; a reshape-and-max compares the
        operand with the stored maximum for equality, which a compiler
        that recomputes the operand in another rounding makes miss."""
        return lax.reduce_window(x, -jnp.inf, lax.max, (1, 2, 2, 1),
                                 (1, 2, 2, 1), "VALID")

    @staticmethod
    def concat(skip, up):
        return jnp.concatenate([skip, up], axis=-1)

    @staticmethod
    def sigmoid(x):
        return jax.nn.sigmoid(x)


def loss_stats(probs, mask):
    """The loss's sufficient statistics of some rows: the sum of the BCE
    terms (logs clamped at -100 as torch's BCELoss), of p*t, of p and of t,
    and the count; targets are ``mask == 1``."""
    t = (mask == 1).astype(jnp.float32)[..., None]
    p = probs.astype(jnp.float32)
    tiny = jnp.finfo(jnp.float32).tiny

    def clog(v):
        return jnp.where(v >= tiny, jnp.log(jnp.maximum(v, tiny)), -100.0)

    bce = jnp.sum(-(t * clog(p) + (1.0 - t) * clog(1.0 - p)))
    return jnp.stack([bce, jnp.sum(p * t), jnp.sum(p), jnp.sum(t),
                      jnp.float32(p.size)])


def loss_from_stats(s, eps: float = 1e-15):
    """BCE (mean) minus the log of the soft Dice over all the rows that
    the statistics were taken over."""
    dice = 2.0 * s[1] / (s[2] + s[3] + eps)
    tiny = jnp.finfo(jnp.float32).tiny
    log_dice = jnp.where(dice >= tiny, jnp.log(jnp.maximum(dice, tiny)), -100.0)
    return s[0] / s[4] - log_dice


def bce_log_dice(probs, mask):
    return loss_from_stats(loss_stats(probs, mask))


def leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def make_loss_and_grad(ref_module, config, mode: str, remat: bool = True,
                       put=jnp.asarray):
    """``(params, state, image, mask, rows) -> (loss, new state, gradient)``
    over the whole batch, in blocks of ``rows`` rows and two passes (one
    block and one pass where ``rows`` is the whole batch). ``image`` and
    ``mask`` are the host's arrays; ``put`` places a block of them on the
    device, or spreads its rows over several."""
    ops = Ops(mode, remat)

    def stats_fn(params, state, image, mask):
        out, new_state = ref_module.forward(ops, config, params, state, image)
        return loss_stats(out, mask), new_state

    def whole(params, state, image, mask):
        def loss_fn(p):
            s, new_state = stats_fn(p, state, image, mask)
            return loss_from_stats(s), new_state

        (loss, new_state), g = jax.value_and_grad(loss_fn, has_aux=True)(params)
        return loss, new_state, g

    def block_grad(params, c, image, mask, acc):
        g = jax.grad(lambda p: jnp.dot(c, stats_fn(p, None, image, mask)[0]))(
            params)
        return g if acc is None else {k: acc[k] + g[k] for k in g}

    whole_j = jax.jit(whole)
    stats_j = jax.jit(lambda p, x, m: stats_fn(p, None, x, m)[0])
    grad_first = jax.jit(lambda p, c, x, m: block_grad(p, c, x, m, None))
    grad_next = jax.jit(block_grad, donate_argnums=(4,))
    finish = jax.jit(jax.value_and_grad(loss_from_stats))

    in_units = None
    if ref_module.stateful:
        import reference_bn
        in_units = reference_bn.Blocks(ref_module, config, mode, put)

    def loss_and_grad(params, state, image, mask, rows):
        n = image.shape[0]
        if rows >= n:
            return whole_j(params, state, put(image), put(mask))
        if in_units is not None:
            return in_units(params, state, image, mask, rows)
        if n % rows:
            raise ValueError(f"{n} rows do not divide into blocks of {rows}")

        def blocks():
            for i in range(0, n, rows):
                yield put(image[i:i + rows]), put(mask[i:i + rows])

        total = None
        for x, m in blocks():
            s = stats_j(params, x, m)
            total = s if total is None else total + s
        loss, c = finish(total)
        g = None
        for x, m in blocks():
            g = grad_first(params, c, x, m) if g is None else grad_next(
                params, c, x, m, g)
        return loss, None, g

    return loss_and_grad


def make_update(config):
    """Adam with L2 folded into the gradient, one jitted call:
    ``(params, m, v, t, g, grad_scale) -> (params, m, v, g as the
    optimiser gets it)``."""
    opt = config["optimizer"]
    lr, wd = opt["lr"], opt["weight_decay"]
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]

    def update(params, m, v, t, g, grad_scale):
        g = {k: g[k] * grad_scale + wd * params[k] for k in params}
        m = {k: b1 * m[k] + (1 - b1) * g[k] for k in g}
        v = {k: b2 * v[k] + (1 - b2) * jnp.square(g[k]) for k in g}
        mhat = 1.0 / (1 - b1 ** t)
        vhat = 1.0 / (1 - b2 ** t)
        new = {k: params[k] - lr * (m[k] * mhat) / (jnp.sqrt(v[k] * vhat) + eps)
               for k in g}
        return new, m, v, g

    return jax.jit(update, donate_argnums=(1, 2, 4))


def follow(ref_module, config, params, state, batches, grad_scale: float,
           mode: str = "f32", rows: int = 0, skip_update: bool = False,
           remat: bool = True, put=jnp.asarray):
    """Follow the steps of ``batches`` from ``params``. Returns the losses,
    the first gradient as the optimiser gets it with its per-leaf norm, the
    per-leaf norm of the parameters' change over all the steps, and the
    last model state. ``batches`` yields the host's ``(image, mask)``
    pairs; ``put`` places a block of their rows on the device, or spreads
    it over several chips, and the reference runs where its inputs live.
    ``rows`` is the block of rows (0: the whole batch at once)."""
    loss_and_grad = make_loss_and_grad(ref_module, config, mode, remat, put)
    update = make_update(config)
    zeros = jax.jit(lambda p: {k: jnp.zeros_like(x) for k, x in p.items()})
    m, v = zeros(params), zeros(params)
    p0, cur, losses, g1 = params, params, [], None
    for i, (image, mask) in enumerate(batches):
        loss, state, g = loss_and_grad(cur, state, image, mask,
                                       rows or image.shape[0])
        del image, mask
        new, m, v, g = update(cur, m, v, jnp.float32(i + 1), g,
                              jnp.float32(grad_scale))
        if i == 0:
            g1 = g
        del g
        if not skip_update:
            cur = new
        losses.append(loss)
    change = jax.jit(lambda a, b: leaf_norms({k: a[k] - b[k] for k in a}))(cur, p0)
    return {
        "losses": [float(x) for x in losses],
        "grad": g1,
        "grad_norms": {k: float(x) for k, x in jax.jit(leaf_norms)(g1).items()},
        "change_norms": {k: float(x) for k, x in change.items()},
        "state": state,
    }
