"""The numbers that decide ``correct``: what the timed path produced
against the plain reference (``bench.reference``), each beside the
limit its configuration states.

Training (the cell's set-up drives the window's own ``Session.run``
from the seed; the reference follows the same call, every round of it):

  loss_gap    the largest relative gap of the first ``CHECK_STEPS``
              steps' losses, |program - reference| / |reference|.
  change_gap  the gap of the parameters' change over the whole call
              (every round and its FedAvg) by the worst leaf: the
              distance between the program's norm of a leaf's change
              and the reference's, over the larger of the reference's
              norm of that leaf and of the median leaf.

Serving (a sample drawn from the seed of the requests the window
finished, and of the exchange stacks the hot cache holds):

  logit_gap   the widest of two gaps, each over the reference's logit
              scale of that row: how far a served class's exchanged
              reference logit lies below the reference's best, and
              how far a cached per-client logit stack lies from the
              reference's.
"""
from __future__ import annotations

import numpy as np

CHECK_STEPS = 16     # short of where sound runs part by their own rounding


def loss_gap(program_losses, reference_losses, steps):
    p = np.asarray(program_losses, np.float64)[:steps]
    r = np.asarray(reference_losses, np.float64)[:steps]
    if p.shape != r.shape or not np.all(np.isfinite(p)):
        return float("inf")
    return float(np.max(np.abs(p - r) / np.abs(r)))


def leaves(tree, prefix=""):
    """{"layer_0/kernel": array, ...} of a nested dict of arrays."""
    out = {}
    for k in sorted(tree):
        v = tree[k]
        name = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(leaves(v, name + "/"))
        else:
            out[name] = np.asarray(v, np.float64)
    return out


def change_gap(program_final, reference_start, reference_final,
               first_grads, worst=True):
    """Gap of the parameter change's norm by the worst leaf (or the
    median leaf).  Leaves whose first reference gradient is under a
    thousandth of the median leaf's move by round-off alone and are
    left out.  Returns (gap, names of the leaves left out)."""
    p, s, r, g = (leaves(t) for t in (program_final, reference_start,
                                      reference_final, first_grads))
    gnorm = {k: np.linalg.norm(v) for k, v in g.items()}
    gmed = np.median(list(gnorm.values()))
    kept = [k for k in r if gnorm[k] >= 1e-3 * gmed]
    left_out = sorted(set(r) - set(kept))
    ref = {k: np.linalg.norm(r[k] - s[k]) for k in kept}
    med = np.median(list(ref.values()))
    gaps = []
    for k in kept:
        if p.get(k) is None or p[k].shape != s[k].shape \
                or not np.all(np.isfinite(p[k])):
            return float("inf"), left_out
        prog = np.linalg.norm(p[k] - s[k])
        gaps.append(abs(prog - ref[k]) / max(ref[k], med))
    gap = max(gaps) if worst else float(np.median(gaps))
    return float(gap), left_out


def logit_gap(served_preds, ref_sum, cached_stacks, ref_stacks):
    """served_preds [K, n_live] classes of K sampled requests with the
    reference's exchanged logits ref_sum [K, C]; cached_stacks and
    ref_stacks [M, n, C] of M sampled cache entries."""
    worst = 0.0
    if len(served_preds):
        preds = np.asarray(served_preds)
        ref_sum = np.asarray(ref_sum, np.float64)
        scale = np.abs(ref_sum).max(axis=1)
        if preds.min() < 0 or preds.max() >= ref_sum.shape[1]:
            return float("inf")
        best = ref_sum.max(axis=1)
        got = np.take_along_axis(ref_sum, preds, axis=1)   # [K, n_live]
        worst = max(worst, float(((best[:, None] - got).max(axis=1)
                                  / scale).max()))
    if len(cached_stacks):
        c = np.asarray(cached_stacks, np.float64)
        r = np.asarray(ref_stacks, np.float64)
        if not np.all(np.isfinite(c)):
            return float("inf")
        err = np.abs(c - r).max(axis=(1, 2)) / np.abs(r).max(axis=(1, 2))
        worst = max(worst, float(err.max()))
    return worst


def verdict(numbers: dict, limits: dict):
    """(correct, [(name, number, limit)]) -- every number must be at or
    under its limit; a missing or non-finite number, or a limit not
    set, fails."""
    rows = [(k, numbers.get(k, float("nan")), limits[k]) for k in limits]
    ok = bool(rows) and all(lim is not None and np.isfinite(v)
                            and v <= lim for _, v, lim in rows)
    return bool(ok), rows
