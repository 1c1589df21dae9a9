"""Inputs of a cell, made on the device from ``--seed`` in one jitted
call each.

The paper's datasets are not in the repository, so each configuration
names a synthetic generator with the dataset's published shape.  The
recipes follow ``repro.data.synthetic`` (class prototypes of Gaussian
blobs plus pixel noise for the image sets; a dense logistic ground
truth with label noise for the tabular ones) but draw with
``jax.random`` on the device, which is far quicker than drawing tens
of millions of normals on the host.  The draws are the benchmark's own:
the program under test receives the arrays through its dataset
registry, and the reference reads the same arrays.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


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


def make(dataset: dict, seed: int):
    """(x_train, y_train, x_test, y_test) for a configuration's
    ``dataset`` block: x on the device, labels on the host.  The first
    ``test_frac`` of the rows are the test set, as in the program's own
    split rule."""
    kw = {k: v for k, v in dataset.items()
          if k not in ("generator", "rows", "test_frac")}
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 0xDA7A)
    x, y = GENERATORS[dataset["generator"]](key, n=dataset["rows"], **kw)
    n_test = int(dataset["rows"] * dataset["test_frac"])
    y = np.asarray(y)
    return x[n_test:], y[n_test:], x[:n_test], y[:n_test]
