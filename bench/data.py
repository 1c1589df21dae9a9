"""What every model family's rows share: the key a seed draws them
from, the program's train/test split, and taking rows by index.

A family's rows (``bench/families/<family>.py``, ``rows``) may be any
pytree of arrays with a leading row axis: dense features, or dense and
categorical fields side by side.  Code outside the family handles them
only through these functions.
"""
from __future__ import annotations

import jax
import numpy as np


def key(seed: int):
    """The key a configuration's rows are drawn from."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), 0xDA7A)


def take(rows, idx):
    """The rows at ``idx`` (an index array or a slice) of every leaf."""
    return jax.tree.map(lambda a: a[idx], rows)


def count(rows) -> int:
    """The number of rows."""
    return int(jax.tree.leaves(rows)[0].shape[0])


def split(x, y, dataset: dict):
    """(x_train, y_train, x_test, y_test) with the labels on the host:
    the first ``test_frac`` of the dataset's rows are the test set, as
    in the program's own split rule."""
    n_test = int(dataset["rows"] * dataset["test_frac"])
    y = np.asarray(y)
    return (take(x, slice(n_test, None)), y[n_test:],
            take(x, slice(None, n_test)), y[:n_test])
