"""The plain reference: De-VertiFL's step, round and forward pass in
straightforward ``jax.numpy``, independent of the program under test.

It imports nothing from ``repro``.  What it shares with the program is
the published protocol (Algorithms 1 and 2 of De-VertiFL) and the
documented conventions that decide which numbers a seed gives:

* partition: image rows dealt round-robin to clients (paper Fig. 2),
  or tabular columns dealt round-robin; each client's columns in
  ascending id order, clients concatenated in client order (the
  canonical column order);
* initialisation: ``init_key, loop_key = split(PRNGKey(seed))``;
  client ``i`` draws from ``split(init_key, n)[i]``, its layer ``l``
  from ``split(client_key, L)[l]``: kernel ``normal * sqrt(2 / fan_in)``,
  bias zero;
* batches: round ``r`` shuffles with
  ``permutation(split(fold_in(loop_key, r), epochs)[e], n_train)`` per
  epoch ``e`` and drops each epoch's last ``n_train % batch`` rows;
* step: every client computes its logits from its own column slice
  (first layer over its slice rows of a full-width ``[F, H]`` kernel),
  the logits are summed over clients (the hidden-output exchange at the
  logits), each client's loss is the cross-entropy of the sum with the
  gradient flowing only through its own logits, then Adam
  (b1 0.9, b2 0.999, eps 1e-8, no clipping) per client;
* round end: FedAvg, the plain mean of every parameter over clients;
  the optimiser state is kept per client.

Every matrix product goes through ``mm``: ``matmul_highest`` (float32,
``Precision.HIGHEST``) for the reference, ``matmul_high`` for the
control: the three-pass bfloat16 product that ``Precision.HIGH`` runs
on a TPU, written out so that it means the same on every backend.
``matmul_bf16`` is the one-pass product of the TPU's default precision.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def matmul_highest(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _split_bf16(a):
    """a = hi + lo + (a remainder) with hi and lo bfloat16 values.
    ``reduce_precision`` rounds where a cast pair could be folded away
    by a compiler allowed to keep excess precision."""
    hi = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    lo = jax.lax.reduce_precision(a - hi, exponent_bits=8, mantissa_bits=7)
    return hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)


def _dot3(a, b):
    """a @ b as three bfloat16 passes with float32 accumulation:
    hi*hi + hi*lo + lo*hi (the lo*lo term is dropped)."""
    ah, al = _split_bf16(a)
    bh, bl = _split_bf16(b)

    def d(x, y):
        return jnp.matmul(x, y, preferred_element_type=jnp.float32)
    return d(ah, bh) + (d(ah, bl) + d(al, bh))


@jax.custom_vjp
def matmul_high(a, b):
    return _dot3(a, b)


def _high_fwd(a, b):
    return _dot3(a, b), (a, b)


def _high_bwd(res, g):
    a, b = res
    return _dot3(g, jnp.swapaxes(b, -1, -2)), _dot3(jnp.swapaxes(a, -1, -2),
                                                     g)


matmul_high.defvjp(_high_fwd, _high_bwd)


def matmul_bf16(a, b):
    return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# partition and layout
# ---------------------------------------------------------------------------
def partition(kind: str, n_features: int, n_clients: int):
    """Per-client ascending feature ids."""
    if kind == "image_rows":
        side = int(round(n_features ** 0.5))
        if side * side != n_features:
            raise ValueError(f"image_rows needs a square image, got "
                             f"{n_features} features")
        return [np.sort((np.arange(c, side, n_clients)[:, None] * side
                         + np.arange(side)[None, :]).reshape(-1))
                for c in range(n_clients)]
    if kind == "round_robin":
        return [np.arange(c, n_features, n_clients)
                for c in range(n_clients)]
    raise ValueError(f"no reference partition {kind!r}")


def canonical(parts):
    """(column order, per-client (offset, size)) of the concatenated
    client slices."""
    order = np.concatenate(parts)
    sizes = [len(p) for p in parts]
    offsets = np.concatenate([[0], np.cumsum(sizes)[:-1]]).astype(int)
    return order, tuple(zip(offsets.tolist(), sizes))


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------
def dims(n_features, hidden, n_hidden, n_classes):
    return [n_features] + [hidden] * n_hidden + [n_classes]


def init_params(seed: int, n_clients: int, layer_dims):
    """Stacked per-client towers ``{"layer_l": {"kernel": [n, in, out],
    "bias": [n, out]}}`` as the seed's initialisation convention draws
    them (module docstring)."""
    init_key, _ = jax.random.split(jax.random.PRNGKey(seed))

    def one(key):
        ks = jax.random.split(key, len(layer_dims) - 1)
        return {f"layer_{i}": {
            "kernel": jax.random.normal(ks[i], (layer_dims[i],
                                                layer_dims[i + 1]),
                                        jnp.float32)
            * (2.0 / layer_dims[i]) ** 0.5,
            "bias": jnp.zeros((layer_dims[i + 1],), jnp.float32)}
            for i in range(len(layer_dims) - 1)}
    return jax.vmap(one)(jax.random.split(init_key, n_clients))


def loop_key(seed: int):
    return jax.random.split(jax.random.PRNGKey(seed))[1]


def client_logits(p, x_i, off, size, mm):
    """One client's tower from its column slice ``x_i`` [B, size]."""
    n_layers = len(p)
    h = jax.nn.relu(mm(x_i, p["layer_0"]["kernel"][off:off + size])
                    + p["layer_0"]["bias"])
    for i in range(1, n_layers - 1):
        h = jax.nn.relu(mm(h, p[f"layer_{i}"]["kernel"])
                        + p[f"layer_{i}"]["bias"])
    last = p[f"layer_{n_layers - 1}"]
    return mm(h, last["kernel"]) + last["bias"]


def client(params, i):
    return jax.tree.map(lambda a: a[i], params)


def logits_stack(params, x, slices, mm):
    """[n, B, C] per-client logits of canonical-order rows ``x``."""
    return jnp.stack([client_logits(client(params, i), x[:, o:o + s], o,
                                    s, mm)
                      for i, (o, s) in enumerate(slices)])


def cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------
def _step(params, mu, nu, t, xb, yb, slices, lr, mm):
    """One De-VertiFL step of every client; returns the new state, the
    loss and the gradients."""
    outs, vjps = [], []
    for i, (o, s) in enumerate(slices):
        out, vjp = jax.vjp(
            lambda p, o=o, s=s: client_logits(p, xb[:, o:o + s], o, s, mm),
            client(params, i))
        outs.append(out)
        vjps.append(vjp)
    total = outs[0]
    for out in outs[1:]:
        total = total + out
    loss, g_total = jax.value_and_grad(cross_entropy)(total, yb)
    grads = jax.tree.map(lambda *g: jnp.stack(g),
                         *[vjp(g_total)[0] for vjp in vjps])
    b1, b2, eps = 0.9, 0.999, 1e-8
    tf = t.astype(jnp.float32) + 1.0
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    params = jax.tree.map(
        lambda p, m, v: p - lr * (m / (1 - b1 ** tf))
        / (jnp.sqrt(v / (1 - b2 ** tf)) + eps), params, mu, nu)
    return params, mu, nu, t + 1, loss, grads


@partial(jax.jit, static_argnames=("slices", "epochs", "batch", "mm"))
def _round(params, mu, nu, t, key, x, y, *, slices, epochs, batch, lr,
           mm):
    n_train = x.shape[0]
    n_batches = n_train // batch
    order = jax.vmap(lambda k: jax.random.permutation(k, n_train))(
        jax.random.split(key, epochs))
    idx = order[:, :n_batches * batch].reshape(epochs * n_batches, batch)

    def body(carry, b):
        params, mu, nu, t = carry
        params, mu, nu, t, loss, _ = _step(params, mu, nu, t, x[b], y[b],
                                           slices, lr, mm)
        return (params, mu, nu, t), loss

    (params, mu, nu, t), losses = jax.lax.scan(body, (params, mu, nu, t),
                                               idx)
    params = jax.tree.map(
        lambda a: jnp.broadcast_to(a.mean(0, keepdims=True), a.shape),
        params)
    return params, mu, nu, t, losses


@partial(jax.jit, static_argnames=("slices", "epochs", "batch", "mm"))
def _first_grads(params, key, x, y, *, slices, epochs, batch, lr, mm):
    """The gradient of the run's first step."""
    n_train = x.shape[0]
    order = jax.random.permutation(jax.random.split(key, epochs)[0],
                                   n_train)
    b = order[:batch]
    zeros = jax.tree.map(jnp.zeros_like, params)
    return _step(params, zeros, zeros, jnp.zeros((), jnp.int32), x[b],
                 y[b], slices, lr, mm)[5]


def train(seed, x, y, slices, layer_dims, *, rounds, epochs, batch, lr,
          mm=matmul_highest):
    """Follow ``rounds`` rounds from the seed's initialisation on
    canonical-order training rows ``x`` [N, F] with labels ``y``.
    Returns a dict with the initial and final parameters, the first
    round's per-step losses and the first step's gradients."""
    slices = tuple(slices)
    params = init_params(seed, len(slices), layer_dims)
    start = params
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    t = jnp.zeros((), jnp.int32)
    lk = loop_key(seed)
    kw = dict(slices=slices, epochs=epochs, batch=batch, lr=lr, mm=mm)
    first = _first_grads(params, jax.random.fold_in(lk, 0), x, y, **kw)
    losses0 = None
    for r in range(rounds):
        params, mu, nu, t, losses = _round(params, mu, nu, t,
                                           jax.random.fold_in(lk, r), x, y,
                                           **kw)
        if r == 0:
            losses0 = losses
    return {"start": start, "final": params, "losses": losses0,
            "first_grads": first}


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("slices", "mm"))
def serve_logits(params, x, *, slices, mm=matmul_highest):
    """Per-client logits [n, B, C] (what the exchange-point cache holds)
    and their exchanged sum [B, C] for canonical-order rows ``x``."""
    stack = logits_stack(params, x, slices, mm)
    return stack, stack.sum(0)
