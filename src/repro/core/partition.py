"""Vertical partitioning (Algorithm 1 line 3) and the canonical
slice-aware layout the protocol engine trains on.

Partitioning distributes dataset features across participants: image
datasets are dealt row-by-row round-robin (Fig. 2); tabular datasets
round-robin or random.

The column-permutation trick
----------------------------
The paper's zero-padding makes every client's first-layer matmul
full-width: zeropad(x_local) @ W touches all F rows of W even though
only F_i of them meet non-zero inputs.  ``canonicalize`` removes that
waste *once at setup* instead of on every step: it permutes the dataset
columns so client i owns the contiguous slice ``[offset_i, offset_i +
F_i)`` of the reordered feature axis.  Reordering columns of x while
keeping W's row init order is semantics-preserving -- the first layer
is a sum over feature columns, and which physical column a feature
lives in is arbitrary -- so random partitions (titanic) remain the same
experiment, just expressed in an engine-friendly order.  The recorded
``perm`` maps canonical column j back to original feature ``perm[j]``,
and ``Layout.apply`` re-expresses any raw [..., F] array in canonical
order.

On the canonical layout the zero-padding masks become contiguous slabs,
the XLA engine path can ``dynamic_slice`` instead of masking, and the
``vfl_matmul`` Pallas kernel can multiply only the client's weight
rows.

Padded client axes
------------------
``Layout.pad(max_clients)`` appends *dead* client slots (empty feature
slice, all-zero mask) so federations with different participant counts
ride arrays of one static client-axis length and can share a single
compiled round function (repro.core.sweep stacks client-count lanes
this way).  ``LayoutArrays.client_mask`` is the runtime 0/1 view of
which slots are live; the protocol engine multiplies it into the
HiddenOutputExchange sum, the FedAvg weighting, and every loss mean,
so dead slots contribute exact zeros and a padded federation's live
clients train bit-for-bit identically to the unpadded run
(tests/test_padded_engine.py pins this).

See docs/ARCHITECTURE.md for the full Layout/LayoutArrays contract.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple, Sequence, Tuple

import numpy as np

from repro.data import registry as DR
from repro.data import vertical as V


def make_partition(dataset: str, n_features: int, n_clients: int, seed=0):
    """Returns list of per-client sorted feature-index arrays.

    The partition strategy comes from the dataset registry entry
    (``repro.data.registry``): "image_rows" deals whole image rows
    round-robin (Fig. 2), "random" assigns features randomly
    (Titanic), "round_robin" interleaves feature columns, and a
    callable entry is invoked as ``(n_features, n_clients, seed)``.
    Unknown dataset names raise with the registered options."""
    kind = DR.get_dataset(dataset).partition
    if callable(kind):
        return kind(n_features, n_clients, seed)
    if kind == "image_rows":
        side = int(round(n_features ** 0.5))
        return V.round_robin_rows(n_clients, side)
    if kind == "random":
        return V.random_features(n_features, n_clients, seed)
    return V.round_robin_features(n_features, n_clients)


def skewed_partition(n_features: int, sizes: Sequence[int], seed=0):
    """A partition with EXPLICIT unequal per-client feature counts: a
    seeded permutation of the feature ids split at the cumulative
    ``sizes`` (each client's ids sorted, like the registry
    strategies).  ``sizes`` must be positive and sum to
    ``n_features``.  The sizes -- and therefore the canonical
    offsets -- are seed-independent, so skewed layouts satisfy the
    sweep engine's cross-seed static-offset requirement just like the
    registry partitions."""
    sizes = tuple(int(s) for s in sizes)
    if not sizes or any(s < 1 for s in sizes):
        raise ValueError(f"sizes must be positive ints, got {sizes}")
    if sum(sizes) != n_features:
        raise ValueError(f"sizes {sizes} sum to {sum(sizes)}, not "
                         f"n_features={n_features}")
    ids = np.random.default_rng(seed).permutation(n_features)
    return [np.sort(p) for p in
            np.split(ids, np.cumsum(sizes)[:-1])]


def masks_for(partition, n_features, dtype=np.float32):
    """[n_clients, n_features] 0/1 masks (the zero-padding operators)."""
    return np.stack([V.feature_mask(idx, n_features, dtype)
                     for idx in partition])


# ---------------------------------------------------------------------------
# canonical slice-aware layout
# ---------------------------------------------------------------------------
class LayoutArrays(NamedTuple):
    """The device-array view of a Layout, threaded through the jitted
    step/round/predict functions (and vmapped over a seed axis -- and
    now a (seed x client-count) lane axis -- by repro.core.sweep,
    exactly like masks used to be):

      masks        [n_clients, n_features] contiguous-slab zeropad
                   masks (canonical column order) -- the masked
                   reference path; dead (padded) clients are all-zero
      offsets      [n_clients] int32 slice starts -- the dynamic_slice
                   path; dead clients hold 0
      sizes        [n_clients] int32 slice lengths -- runtime view of
                   Layout.sizes for shape-uniform (padded-sweep) first
                   layers; dead clients hold 0
      client_mask  [n_clients] float 1.0 = live participant, 0.0 =
                   dead padding slot.  Multiplied into the exchange
                   sum, FedAvg weights, and loss means so dead slots
                   contribute exact zeros.
    """
    masks: object
    offsets: object
    sizes: object
    client_mask: object


@dataclass(frozen=True, eq=False)
class Layout:
    """Canonical block-aligned feature layout for one federation.

    partition   per-client ORIGINAL feature ids (what each client owns)
    perm        [F] canonical column j holds original feature perm[j]
    inv_perm    [F] original feature f lives at canonical column
                inv_perm[f]
    offsets     per-client canonical slice starts (python ints: static
                under jit, the ``vfl_matmul`` kernel's W-row offsets)
    sizes       per-client slice lengths F_i (0 for dead padding slots)
    n_real      number of LIVE participants; clients [n_real,
                n_clients) are dead padding slots added by ``pad``
    """
    partition: Tuple[np.ndarray, ...]
    perm: np.ndarray
    inv_perm: np.ndarray
    offsets: Tuple[int, ...]
    sizes: Tuple[int, ...]
    n_features: int
    n_real: int

    @property
    def n_clients(self) -> int:
        """Padded client-axis length (== n_real for unpadded layouts)."""
        return len(self.sizes)

    def apply(self, x):
        """Re-express raw [..., F] data in canonical column order."""
        return x[..., self.perm]

    def masks(self, dtype=np.float32):
        """Contiguous-slab zeropad masks in canonical column order.
        Dead (padded) clients get all-zero rows."""
        m = np.zeros((self.n_clients, self.n_features), dtype)
        for i, (off, sz) in enumerate(zip(self.offsets, self.sizes)):
            m[i, off:off + sz] = 1
        return m

    def client_mask(self, dtype=np.float32):
        """[n_clients] 1.0 for live participants, 0.0 for padding."""
        return (np.arange(self.n_clients) < self.n_real).astype(dtype)

    def pad(self, max_clients: int) -> "Layout":
        """Append dead client slots until the client axis has length
        ``max_clients``.  Dead slots own no features (empty slice at
        offset 0, all-zero mask); the protocol engine excludes them
        from the exchange and FedAvg via ``client_mask``."""
        if max_clients < self.n_clients:
            raise ValueError(f"max_clients={max_clients} < existing "
                             f"client axis {self.n_clients}")
        k = max_clients - self.n_clients
        if k == 0:
            return self
        import dataclasses
        empty = tuple(np.empty((0,), self.partition[0].dtype)
                      for _ in range(k))
        return dataclasses.replace(
            self, partition=self.partition + empty,
            offsets=self.offsets + (0,) * k,
            sizes=self.sizes + (0,) * k)

    def arrays(self) -> LayoutArrays:
        import jax.numpy as jnp
        return LayoutArrays(masks=jnp.asarray(self.masks()),
                            offsets=jnp.asarray(self.offsets, jnp.int32),
                            sizes=jnp.asarray(self.sizes, jnp.int32),
                            client_mask=jnp.asarray(self.client_mask()))


def canonicalize(partition, n_features: int) -> Layout:
    """Build the canonical contiguous layout for a partition: column j
    of the canonical order is original feature ``perm[j]``, client i's
    features occupy ``[offset_i, offset_i + F_i)``."""
    parts = tuple(np.asarray(p) for p in partition)
    perm = np.concatenate(parts).astype(np.int64)
    if perm.size != n_features or np.unique(perm).size != n_features:
        raise ValueError("partition must be disjoint and cover all "
                         f"{n_features} features (got {perm.size} ids, "
                         f"{np.unique(perm).size} unique)")
    inv_perm = np.empty_like(perm)
    inv_perm[perm] = np.arange(n_features)
    sizes = tuple(int(len(p)) for p in parts)
    offsets = tuple(int(o) for o in
                    np.concatenate([[0], np.cumsum(sizes)[:-1]]))
    return Layout(partition=parts, perm=perm, inv_perm=inv_perm,
                  offsets=offsets, sizes=sizes, n_features=n_features,
                  n_real=len(parts))


def make_layout(dataset: str, n_features: int, n_clients: int,
                seed=0, max_clients=None, sizes=None) -> Layout:
    """Partition + canonicalize (+ optional padding) in one call.
    ``sizes`` overrides the registry partition strategy with a skewed
    split of explicit per-client feature counts
    (:func:`skewed_partition`); every engine lane -- masked, slice,
    pallas, padded or not -- trains identically on skewed and equal
    splits (tests/test_wire.py pins it)."""
    if sizes is not None:
        if len(sizes) != n_clients:
            raise ValueError(f"sizes has {len(sizes)} entries for "
                             f"n_clients={n_clients}")
        part = skewed_partition(n_features, sizes, seed=seed)
    else:
        part = make_partition(dataset, n_features, n_clients, seed=seed)
    lay = canonicalize(part, n_features)
    return lay if max_clients is None else lay.pad(max_clients)
