"""The paper's MLP towers (De-VertiFL, ``repro.models.PaperMLP``): the
model family of the ``mnist5`` and ``bank2`` configurations.

A model family is everything of a configuration that depends on its
architecture: how its rows are drawn, how the program is asked for it,
how the plain reference follows it, and how much work a step is.  The
harness finds this file by the configuration's ``model.family`` and
calls nothing else of it (``bench/README.md``, "Adding a model
family").

The paper's datasets are not in the repository, so each configuration
names a synthetic generator with the dataset's published shape.  The
recipes follow ``repro.data.synthetic`` (class prototypes of Gaussian
blobs plus pixel noise for the image sets; a dense logistic ground
truth with label noise for the tabular ones) but draw with
``jax.random`` on the device, in one jitted call each, which is far
quicker than drawing tens of millions of normals on the host.  The
draws are the benchmark's own: the program receives the arrays through
its dataset registry, and the reference reads the same arrays.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import data, program, reference, work


# ---------------------------------------------------------------------------
# rows
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("n", "side", "n_classes", "blobs"))
def image_like(key, *, n, side, n_classes, noise, proto_scale, blobs):
    """[n, side*side] float32 images in [0, 1] and [n] int32 labels."""
    kp, kl, kn = jax.random.split(key, 3)
    kc, ks, ka, kg = jax.random.split(kp, 4)
    centres = jax.random.uniform(kc, (n_classes, blobs, 2), jnp.float32,
                                 2.0, side - 2.0)
    widths = jax.random.uniform(ks, (n_classes, blobs, 2), jnp.float32,
                                1.5, 5.0)
    amps = jax.random.uniform(ka, (n_classes, blobs), jnp.float32, 0.4,
                              1.0)
    amps = amps * jnp.where(jax.random.bernoulli(kg, 0.5, amps.shape),
                            1.0, -1.0)
    grid = jnp.arange(side, dtype=jnp.float32)
    gy, gx = grid[:, None, None, None], grid[None, :, None, None]
    blob = amps * jnp.exp(-(((gx - centres[..., 0]) / widths[..., 0]) ** 2
                            + ((gy - centres[..., 1]) / widths[..., 1])
                            ** 2))
    protos = blob.sum(-1).transpose(2, 0, 1)              # [C, side, side]
    protos = protos / jnp.abs(protos).max(axis=(1, 2), keepdims=True)
    labels = jax.random.randint(kl, (n,), 0, n_classes, jnp.int32)
    imgs = (protos.reshape(n_classes, -1)[labels] * proto_scale
            + noise * jax.random.normal(kn, (n, side * side), jnp.float32))
    return jnp.clip((imgs + 1.0) * 127.5, 0.0, 255.0) / 255.0, labels


@partial(jax.jit, static_argnames=("n", "n_features"))
def tabular(key, *, n, n_features, flip, sharpness):
    """[n, n_features] float32 standard-normal rows and [n] int32 binary
    labels from a dense logistic ground truth over every feature."""
    kx, kw, ky, kf = jax.random.split(key, 4)
    x = jax.random.normal(kx, (n, n_features), jnp.float32)
    w = jax.random.normal(kw, (n_features,), jnp.float32)
    logits = jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST) \
        / np.sqrt(n_features)
    y = jax.random.uniform(ky, (n,)) < jax.nn.sigmoid(sharpness * logits)
    y = jnp.where(jax.random.uniform(kf, (n,)) < flip, ~y, y)
    return x, y.astype(jnp.int32)


GENERATORS = {"image_like": image_like, "tabular": tabular}


def rows(config: dict, seed: int):
    """(x_train, y_train, x_test, y_test) of the configuration's
    ``dataset`` block: dense ``[N, F]`` rows on the device, labels on
    the host."""
    ds = config["dataset"]
    kw = {k: v for k, v in ds.items()
          if k not in ("generator", "rows", "test_frac")}
    x, y = GENERATORS[ds["generator"]](data.key(seed), n=ds["rows"], **kw)
    return data.split(x, y, ds)


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------
def session(config: dict, arrays, seed: int, *, rounds: int,
            eval_every: int):
    """Register ``arrays`` (``rows``) with the program as the
    configuration's dataset and build its Session; refuses a model that
    is not the configuration's."""
    from repro.data.registry import register_dataset
    m = config["model"]
    register_dataset(program.dataset_name(config),
                     make=lambda n=None, seed=None, test_frac=0.2: arrays,
                     n_classes=m["n_classes"], arch=m["arch"],
                     partition=config["federation"]["partition"],
                     overwrite=True)
    sess = program.build(config, seed, rounds=rounds,
                         eval_every=eval_every)
    got = {k: getattr(sess.federation.model, k)
           for k in ("in_features", "hidden", "n_hidden", "n_classes")}
    want = {k: m[k] for k in got}
    if got != want:
        raise SystemExit(f"model {m['arch']!r} is {got}, the configuration "
                         f"states {want}")
    return sess


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------
def _layout(config):
    """(column order, per-client slices) of the reference's canonical
    layout, and the layer widths."""
    m, fed = config["model"], config["federation"]
    order, slices = reference.canonical(reference.partition(
        fed["partition"], m["in_features"], fed["n_clients"]))
    dims = reference.dims(m["in_features"], m["hidden"], m["n_hidden"],
                          m["n_classes"])
    return order, slices, dims


def follow(config: dict, arrays, seed: int, rounds: int, mm=None,
           device=None):
    """The reference (or, with a lower product ``mm``, the control) from
    the seed through ``rounds`` rounds of the training rows of
    ``arrays``: ``{"losses", "start", "final", "first_grads"}``."""
    trn = config["training"]
    order, slices, dims = _layout(config)
    x, y = arrays[0][:, order], jnp.asarray(arrays[1])
    with jax.default_device(device):
        x, y = jax.device_put((x, y), device)
        return reference.train(
            seed, x, y, slices, dims, rounds=rounds,
            epochs=trn["epochs"], batch=trn["batch_size"], lr=trn["lr"],
            mm=mm or reference.matmul_highest)


@partial(jax.jit, static_argnames=("n_clients", "layer_dims"))
def _towers(key, *, n_clients, layer_dims):
    """Stacked per-client towers drawn from the key: He-normal kernels,
    normal(0, 0.1) biases."""
    def one(k):
        ks = jax.random.split(k, 2 * (len(layer_dims) - 1))
        return {f"layer_{i}": {
            "kernel": jax.random.normal(ks[2 * i], layer_dims[i:i + 2])
            * (2.0 / layer_dims[i]) ** 0.5,
            "bias": 0.1 * jax.random.normal(ks[2 * i + 1],
                                            (layer_dims[i + 1],))}
            for i in range(len(layer_dims) - 1)}
    return jax.vmap(one)(jax.random.split(key, n_clients))


def serve_params(config: dict, key):
    """The parameters a serving cell serves, drawn from ``key``."""
    return _towers(key, n_clients=config["federation"]["n_clients"],
                   layer_dims=tuple(_layout(config)[2]))


def serve_logits(config: dict, params, x, mm=None):
    """The reference's per-client logits [n, B, C] (what the exchange
    cache holds) and their exchanged sum [B, C] for test rows ``x`` in
    the dataset's own column order."""
    order, slices, _ = _layout(config)
    return reference.serve_logits(params, jnp.asarray(np.asarray(x)[:, order]),
                                  slices=slices,
                                  mm=mm or reference.matmul_highest)


# ---------------------------------------------------------------------------
# work
# ---------------------------------------------------------------------------
def context(config: dict) -> dict:
    """What the metric readers read of this model (``ctx["run"]``): the
    live clients' first-layer slice widths, the tower's shape, and the
    step's matmul FLOPs per sample (``bench.work``)."""
    m = config["model"]
    widths = [size for _, size in _layout(config)[1]]
    shape = {"hidden": m["hidden"], "n_hidden": m["n_hidden"],
             "n_classes": m["n_classes"]}
    return {"widths": widths, **shape,
            "step_flops_per_sample": work.step_flops_per_sample(
                widths, **shape)}
