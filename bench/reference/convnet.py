"""Plain float32 reference of the federated round, from a config's layer list.

Written against ``jax.numpy`` and ``jax.lax`` alone; it imports nothing of
the system under test.  It covers what the benchmark's cells compare:

* the conv-net forward pass (3x3 SAME convolutions, 2x2 max-pools, dense
  layers, ReLU after every layer but the last) from ``config["layers"]``;
* masked local SGD: ``H`` steps, each on the mean loss over the step's
  valid samples (mask 1), a client's loss being the mean of its H step
  losses;
* the eq.-(13) weighted average of client models (weights normalised by
  their sum);
* the staleness-weighted cross-region merge,
  ``w_i ~ mass_i * 2^(-staleness_i / half_life)``.

``prec`` selects the arithmetic.  ``"f32"`` is the reference itself:
float32 storage, every convolution and matmul at ``Precision.HIGHEST``.
The control, which must fail the comparison, is ``"bf16"``: parameters,
activations, gradients and updates held in bfloat16.  ``"default"`` keeps
float32 storage and computes every product at ``Precision.DEFAULT``, on a
TPU one bfloat16 pass with float32 accumulation: the arithmetic the
configuration states for the program, a witness of what that precision
alone does to the compared numbers.
"""
from __future__ import annotations

import math
import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
DEFAULT = jax.lax.Precision.DEFAULT
PRECISIONS = ("f32", "bf16", "default")


def weight_layers(config) -> list:
    """``[(kind, fan_in, shape)]`` of every layer that holds weights."""
    h, w, c = config["input_shape"]
    out = []
    for layer in config["layers"]:
        kind = layer[0]
        if kind == "conv":
            out.append(("conv", 9 * c, (3, 3, c, layer[1])))
            c = layer[1]
        elif kind == "pool":
            h, w = h // 2, w // 2
        elif kind == "dense":
            din = h * w * c
            out.append(("dense", din, (din, layer[1])))
            h, w, c = 1, 1, layer[1]
        else:
            raise ValueError(f"unknown layer kind {kind!r}")
    return out


def init_params(config, key):
    """He-normal weights and zero biases, one key per layer from
    ``split(key, n_layers)``: ``[{"b": (cout,), "w": shape}, ...]``."""
    layers = weight_layers(config)
    keys = jax.random.split(key, len(layers))
    params = []
    for k, (_, fan_in, shape) in zip(keys, layers):
        std = math.sqrt(2.0 / fan_in)
        params.append({"b": jnp.zeros((shape[-1],), jnp.float32),
                       "w": jax.random.normal(k, shape, jnp.float32) * std})
    return params


def conv3x3(x, w, precision=HIGHEST):
    """3x3 SAME cross-correlation of ``x`` (NHWC) with ``w`` (HWIO), as one
    matrix product of the nine shifted copies of ``x`` against ``w``."""
    n, h, wd, c = x.shape
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    cols = jnp.concatenate([xp[:, i:i + h, j:j + wd, :]
                            for i in range(3) for j in range(3)], axis=-1)
    return jnp.dot(cols, w.reshape(9 * c, w.shape[-1]), precision=precision)


def forward(config, params, x, prec: str = "f32"):
    """Logits of a batch ``x`` (N, H, W, C)."""
    dt = jnp.bfloat16 if prec == "bf16" else jnp.float32
    precision = DEFAULT if prec == "default" else HIGHEST
    x = x.astype(dt)
    n_weighted = sum(1 for layer in config["layers"] if layer[0] != "pool")
    li = 0
    for layer in config["layers"]:
        if layer[0] == "pool":
            n, h, w, c = x.shape
            x = x.reshape(n, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))
            continue
        p = params[li]
        li += 1
        wt = p["w"].astype(dt)
        if layer[0] == "conv":
            x = conv3x3(x, wt, precision)
        else:
            x = jnp.dot(x.reshape(x.shape[0], -1), wt, precision=precision)
        x = x + p["b"].astype(dt)
        if li < n_weighted:
            x = jnp.maximum(x, 0)
    return x


def masked_loss(config, params, x, y, m, prec: str = "f32"):
    """Mean negative log-likelihood over the valid samples of a batch."""
    logits = forward(config, params, x, prec).astype(jnp.float32)
    z = logits - jnp.max(logits, axis=-1, keepdims=True)
    logp = z - jnp.log(jnp.sum(jnp.exp(z), axis=-1, keepdims=True))
    nll = -jnp.take_along_axis(logp, y[:, None].astype(jnp.int32), axis=-1)
    return jnp.sum(nll[:, 0] * m) / jnp.maximum(jnp.sum(m), 1.0)


class LocalSGD:
    """One client's H masked SGD steps, compiled once per batch shape.

    ``LocalSGD(config, prec)(params, xs, ys, mask, lr)`` with xs
    (H, B, ...) and ys/mask (H, B) returns (new params in float32, mean
    of the H step losses)."""

    def __init__(self, config, prec: str = "f32"):
        if prec not in PRECISIONS:
            raise ValueError(f"prec {prec!r} not in {PRECISIONS}")
        dt = jnp.bfloat16 if prec == "bf16" else jnp.float32

        def run(params, xs, ys, mask, lr):
            params = jax.tree_util.tree_map(lambda a: a.astype(dt), params)

            def step(p, batch):
                x, y, m = batch
                loss, g = jax.value_and_grad(
                    lambda q: masked_loss(config, q, x, y, m, prec))(p)
                p = jax.tree_util.tree_map(
                    lambda a, b: a - (lr * b).astype(a.dtype), p, g)
                return p, loss

            p, losses = jax.lax.scan(step, params, (xs, ys, mask))
            return (jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float32), p), jnp.mean(losses))

        self._run = jax.jit(run)

    def __call__(self, params, xs, ys, mask, lr):
        return self._run(params, xs, ys, mask, np.float32(lr))


@jax.jit
def _axpy(acc, w, p):
    return jax.tree_util.tree_map(lambda a, b: a + w * b, acc, p)


def weighted_average(models, weights):
    """eq. (13): ``sum_i w_i model_i / sum_i w_i`` (elementwise float32)."""
    w = np.asarray(weights, np.float64)
    w = (w / w.sum()).astype(np.float32)
    acc = jax.tree_util.tree_map(jnp.zeros_like, models[0])
    for wi, m in zip(w, models):
        acc = _axpy(acc, wi, m)
    return acc


def merge_weights(masses, staleness, half_life):
    """Cross-region merge weights: data mass discounted by model age."""
    w = np.asarray(masses, np.float64)
    s = np.asarray(staleness, np.float64)
    if half_life is not None:
        w = w * np.exp2(-s / float(half_life))
    return w / w.sum()


def region_round(sgd: LocalSGD, params, buckets, lr, total, sample=()):
    """One region's FL round over a bucketed cohort.

    ``buckets`` is ``[(xs, ys, mask, sizes, n_real)]``: each bucket's
    padded tensors, per-slot pool sizes and number of real clients (its
    leading slots).  Every real client trains from ``params`` on its own
    rows, one at a time; padding slots hold no client.  Returns (the
    eq.-(13) average with weights = pool size / ``total``, the region's
    rows; per-client losses in bucket order; the trained models of the
    clients ``(bucket, slot)`` in ``sample``).  The average accumulates
    client by client, so one client model at a time is held besides it."""
    acc = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, clients = [], {}
    for b, (xs, ys, mask, sizes, n_real) in enumerate(buckets):
        for c in range(n_real):
            new, loss = sgd(params, xs[c], ys[c], mask[c], lr)
            acc = _axpy(acc, np.float32(float(sizes[c]) / total), new)
            losses.append(loss)
            if (b, c) in sample:
                clients[(b, c)] = [np.asarray(a) for a in
                                   jax.tree_util.tree_leaves(new)]
    return acc, [float(v) for v in losses], clients
