"""The plain reference of the GCN cell: one training step of OGB's GCN
baseline for ogbn-arxiv (arXiv:2005.00687) in jnp float32.

Nothing here imports the system under test or takes anything it made: the
graph is normalised here from the benchmark's own edges (``datagen.Coo``),
aggregation is ``reference.spmm``'s chunked segment sum (its backward the
same sum over the transposed coordinates), and Adam is written out.  The
inputs are the benchmark's features, labels, train mask and dropout key,
and the parameters and Adam moments a step starts from.

The model, as the baseline states it: layer ``i`` is ``Â (h W_i)`` with
``Â = D^-1/2 (S + I) D^-1/2`` (``S`` the symmetrised edge pattern, self
loops set to one); hidden layers go on through batch norm over all nodes
(biased variance), ReLU and dropout (mask from ``fold_in(key, i)``, kept
entries scaled by ``1 / (1 - p)``); the last adds its bias; the loss is the
mean log-softmax NLL over the train nodes.  The hidden layers have no bias,
since batch norm cancels it.

``precision`` is ``reference``'s: ``"highest"`` is float32 throughout (the
dense products under ``jax.default_matmul_precision("highest")``);
``"high"`` and ``"default"`` split every product's operands into bfloat16
passes, written out, so that they read the same on every platform.
"""
from __future__ import annotations

import functools

import numpy as np

from bench import datagen, reference


def adjacency(rows, cols, n: int) -> tuple:
    """(Â, Âᵀ) as sorted ``datagen.Coo``."""
    loop = np.arange(n, dtype=np.int64)
    r = np.concatenate([np.asarray(rows, np.int64),
                        np.asarray(cols, np.int64), loop])
    c = np.concatenate([np.asarray(cols, np.int64),
                        np.asarray(rows, np.int64), loop])
    lin = np.unique(r * n + c)
    r, c = lin // n, lin % n
    deg = np.bincount(r, minlength=n).astype(np.float64)
    vals = (1.0 / np.sqrt(deg[r] * deg[c])).astype(np.float32)
    order = np.lexsort((r, c))
    a = datagen.Coo(r.astype(np.int32), c.astype(np.int32), vals, (n, n))
    at = datagen.Coo(c[order].astype(np.int32), r[order].astype(np.int32),
                     vals[order], (n, n))
    return a, at


def _matmul(a, b, precision: str):
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    if precision == "highest":
        return jnp.dot(a, b, precision=hi)
    ah, al = reference._split(a)
    bh, bl = reference._split(b)
    if precision == "default":
        return jnp.dot(ah, bh, precision=hi)
    return jnp.dot(ah, bh, precision=hi) + (jnp.dot(ah, bl, precision=hi)
                                            + jnp.dot(al, bh, precision=hi))


def _propagate(n: int, precision: str):
    """``h -> Â h`` by segment sums, whose VJP is the same sum over Âᵀ."""
    import jax

    @jax.custom_vjp
    def prop(h, a, at):
        return reference.spmm(a, h, n, precision)

    def fwd(h, a, at):
        return prop(h, a, at), (a, at)

    def bwd(res, dy):
        a, at = res
        return reference.spmm(at, dy, n, precision), None, None

    prop.defvjp(fwd, bwd)
    return prop


def loss(params, x, y, train, key_data, a, at, *, dims, dropout: float,
         bn_eps: float, precision: str):
    """Mean NLL over the train nodes of one training-mode pass."""
    import jax
    import jax.numpy as jnp
    n = x.shape[0]
    prop = _propagate(n, precision)
    key = jax.random.wrap_key_data(key_data)
    h = x
    layers = len(dims) - 1
    for i in range(layers):
        p = params[f"conv{i}"]
        h = prop(_matmul(h, p["w"], precision), a, at)
        if i == layers - 1:
            h = h + p["b"]
            break
        mean = jnp.mean(h, axis=0)
        var = jnp.mean(jnp.square(h - mean), axis=0)
        h = (h - mean) / jnp.sqrt(var + bn_eps) * p["bn_scale"] \
            + p["bn_shift"]
        h = jnp.maximum(h, 0.0)
        keep = jax.random.bernoulli(jax.random.fold_in(key, i),
                                    1.0 - dropout, h.shape)
        h = jnp.where(keep, h / (1.0 - dropout), 0.0)
    logp = h - jax.scipy.special.logsumexp(h, axis=-1, keepdims=True)
    nll = -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
    return jnp.sum(nll * train) / jnp.sum(train)


@functools.lru_cache(maxsize=None)
def _step_jit(dims: tuple, dropout: float, bn_eps: float, adam: tuple,
              precision: str):
    import jax
    import jax.numpy as jnp
    lr, b1, b2, eps, wd = adam

    def step(params, m, v, count, x, y, train, key_data, a, at):
        with jax.default_matmul_precision("highest"):
            value, grads = jax.value_and_grad(loss)(
                params, x, y, train, key_data, a, at, dims=dims,
                dropout=dropout, bn_eps=bn_eps, precision=precision)
        t = (count + 1).astype(jnp.float32)

        def adam_step(p, g, m_, v_):
            m_ = b1 * m_ + (1 - b1) * g
            v_ = b2 * v_ + (1 - b2) * g * g
            mh = m_ / (1 - b1 ** t)
            vh = v_ / (1 - b2 ** t)
            return p - lr * (mh / (jnp.sqrt(vh) + eps) + wd * p)

        new = jax.tree.map(adam_step, params, grads, m, v)
        return value, grads, new

    return jax.jit(step)


def train_step(cfg: dict, graph: tuple, params, m, v, count, x, y, train,
               key_data, precision: str = "highest"):
    """``(loss, grads, new_params)`` of one Adam step from ``params`` and
    the moments ``m``, ``v`` after ``count`` steps, for the configuration
    ``cfg`` (its widths, dropout, ``bn_eps`` and ``adam``).  ``graph`` is
    ``(Â, Âᵀ)`` as :func:`chunks_of` gives them."""
    reference._check_precision(precision)
    dims = ((cfg["in_dim"],) + (cfg["hidden"],) * (cfg["num_layers"] - 1)
            + (cfg["num_classes"],))
    ad = cfg["adam"]
    fn = _step_jit(dims, float(cfg["dropout"]), float(cfg["bn_eps"]),
                   (float(ad["lr"]), float(ad["beta1"]), float(ad["beta2"]),
                    float(ad["eps"]), float(ad["weight_decay"])), precision)
    return fn(params, m, v, count, x, y, train, key_data, *graph)


def chunks_of(coo) -> tuple:
    """(Â, Âᵀ) of ``coo``'s edges as ``reference.chunks`` arrays."""
    a, at = adjacency(coo.rows, coo.cols, coo.shape[0])
    return reference.chunks(a), reference.chunks(at)
