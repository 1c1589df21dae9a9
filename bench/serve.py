"""Serving traffic: open-loop split-feature requests against one
``FederatedServer``.

The traffic file gives the offered rate, the entity popularity
(Zipf exponent over the test rows), the slot pool and the hot cache.
Arrivals are a Poisson stream of a fixed count, ``rate * seconds``
requests at sorted uniform times, so every seed offers the same load
in another order.  Each request is announced with ``submit`` and its
clients' column slices (``split_features`` of the test rows) are
offered in a random client order; the server's ``step`` is driven
whenever nothing is due.  A request's latency runs from the time it
was due to the step that completed it, so a stalled generator shows
as latency.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from bench import catalog, check, data


class ServeCell:
    def __init__(self, cell: dict, seed: int):
        self.config, self.traffic = cell["config"], cell["traffic"]
        self.seed = seed
        self.rng = np.random.default_rng(seed)
        name = self.config["model"]["family"]
        self.family = catalog.family(name)
        missing = [f for f in ("serve_params", "serve_logits")
                   if not hasattr(self.family, f)]
        if missing:
            raise SystemExit(f"model family {name!r} has no "
                             f"{' or '.join(missing)}: it cannot serve")

    # ------------------------------------------------------------------
    def setup(self):
        from repro.api import split_features
        cfg, trf = self.config, self.traffic
        self.arrays = jax.block_until_ready(
            self.family.rows(cfg, self.seed))
        self.marks = [("data", time.perf_counter())]
        sess = self.family.session(cfg, self.arrays, self.seed, rounds=1,
                                   eval_every=0)
        fed = sess.federation
        self.marks.append(("session", time.perf_counter()))
        self.params = self.family.serve_params(
            cfg, jax.random.fold_in(jax.random.PRNGKey(self.seed), 0x5E7E))
        self.srv = sess.server(params=self.params,
                               max_slots=trf["max_slots"],
                               cache=trf["cache"])
        self.spec_hash = sess.spec.spec_hash
        self.xte = jax.tree.map(np.asarray, self.arrays[2])
        self.n_rows = data.count(self.xte)
        self.n_live = cfg["federation"]["n_clients"]
        self.slices = split_features(fed.layout, self.xte)
        ranks = np.arange(1, self.n_rows + 1, dtype=np.float64)
        self.popularity = ranks ** -trf["zipf"]
        self.popularity /= self.popularity.sum()
        self.row_of_rank = self.rng.permutation(self.n_rows)
        self.next_uid = 0
        self.request_rows = {}
        # warm-up: a burst from the same popularity, so the step is
        # compiled and the hot cache holds what a running server holds
        self._serve(self._stream(trf["warmup_requests"], None))
        self.marks.append(("warm-up", time.perf_counter()))

    def _stream(self, n, seconds):
        """n requests: due times (None: all at once), rows and client
        orders."""
        due = (np.zeros(n) if seconds is None
               else np.sort(self.rng.uniform(0.0, seconds, n)))
        rows = self.row_of_rank[self.rng.choice(self.n_rows, n,
                                                p=self.popularity)]
        orders = np.argsort(self.rng.random((n, self.n_live)), axis=1)
        uids = np.arange(self.next_uid, self.next_uid + n)
        self.next_uid += n
        self.request_rows.update(zip(uids.tolist(), rows.tolist()))
        return uids, due, rows, orders

    def _serve(self, stream, drain_s=60.0):
        """Drive the server through ``stream``; returns (done, late,
        assembly seconds, steps, wall)."""
        from repro.api import ServeRequest
        srv, slices = self.srv, self.slices
        uids, due, rows, orders = stream
        n = len(uids)
        done = np.full(n, np.nan)
        late = np.zeros(n)
        assembly = 0.0
        steps0 = srv.steps
        base = int(uids[0]) if n else 0
        i = 0
        t0 = time.perf_counter()
        deadline = (due[-1] if n else 0.0) + drain_s
        while i < n or srv.queued or srv.occupancy:
            now = time.perf_counter() - t0
            if now > deadline:
                break
            while i < n and due[i] <= now:
                a = time.perf_counter()
                uid, row = int(uids[i]), int(rows[i])
                srv.submit(ServeRequest(uid=uid, entity_id=row))
                for c in orders[i]:
                    srv.offer(uid, int(c), slices[int(c)][row])
                b = time.perf_counter()
                assembly += b - a
                late[i] = a - t0 - due[i]
                i += 1
            k = srv.step()
            if k:
                t = time.perf_counter() - t0
                for rec in srv.telemetry[-k:]:
                    done[rec["uid"] - base] = t
            elif i < n:
                wait = due[i] - (time.perf_counter() - t0)
                if wait > 2e-3:
                    time.sleep(wait - 1e-3)
        wall = time.perf_counter() - t0
        return done - due, late, assembly, srv.steps - steps0, wall

    # ------------------------------------------------------------------
    def window(self, seconds: float) -> dict:
        trf, srv = self.traffic, self.srv
        n = int(round(trf["rate_per_s"] * seconds))
        hits0, miss0 = srv.cache.hits, srv.cache.misses
        self.window_uids = np.arange(self.next_uid, self.next_uid + n)
        lat, late, assembly, steps, wall = self._serve(
            self._stream(n, seconds))
        self.attempted = n
        self.failed = int(np.isnan(lat).sum())
        lat = lat[~np.isnan(lat)]
        hits, misses = srv.cache.hits - hits0, srv.cache.misses - miss0
        self.counters = {"requests": n, "steps": steps, "wall_s": wall,
                         "assembly_s": assembly, "cache_hits": hits,
                         "cache_misses": misses}
        self.lateness_ms = np.percentile(late, [50, 95, 100]) * 1e3
        if not len(lat):
            return {}
        # the tail is reported on stderr only: host stalls swing it past
        # any bound a check could hold (PERF.md)
        self.p95_ms = float(np.percentile(lat, 95) * 1e3)
        return {"serve_p50_ms": float(np.percentile(lat, 50) * 1e3)}

    def traced(self):
        seconds = self.traffic["trace_seconds"]
        n = int(round(self.traffic["rate_per_s"] * seconds))
        _, _, _, steps, _ = self._serve(self._stream(n, seconds))
        return {"steps": steps}

    def notes(self):
        c, late = self.counters, self.lateness_ms
        return [f"window: {c['requests']} requests, {c['steps']} steps, "
                f"{c['wall_s']:.3f} s to the last answer, latency p95 "
                f"{getattr(self, 'p95_ms', float('nan')):.4f} ms",
                f"generator late by p50 {late[0]:.4f} ms, p95 "
                f"{late[1]:.4f} ms, max {late[2]:.4f} ms"]

    def release(self):
        self.arrays = None

    # ------------------------------------------------------------------
    def numbers(self, mm=None):
        """The compared number of this run.  With ``mm`` the reference
        at that product stands in for the program (the control)."""
        cfg, trf, srv = self.config, self.traffic, self.srv
        logits = self.family.serve_logits
        rng = np.random.default_rng([self.seed, 0xC4EC])
        window = [u for u in self.window_uids.tolist() if u in srv.results]
        pick = np.sort(rng.choice(len(window),
                                  min(trf["check_requests"], len(window)),
                                  replace=False))
        uids = [window[j] for j in pick]
        rows = np.asarray([self.request_rows[u] for u in uids], int)
        held = sorted({self.request_rows[u] for u in window
                       if (self.spec_hash, self.request_rows[u])
                       in srv.cache})
        held = np.sort(rng.choice(held, min(trf["check_cached"], len(held)),
                                  replace=False)) if held else \
            np.zeros(0, int)
        x_rows, x_held = data.take(self.xte, rows), data.take(self.xte, held)
        _, ref_sum = logits(cfg, self.params, x_rows)
        ref_held = np.asarray(logits(cfg, self.params, x_held)[0]
                              ).transpose(1, 0, 2)
        if mm is None:          # what the program served and cached
            preds = np.stack([srv.results[u] for u in uids]) if uids \
                else np.zeros((0, self.n_live), int)
            cached = np.stack([np.asarray(srv.cache.lookup(
                (self.spec_hash, int(e)))) for e in held]) if len(held) \
                else np.zeros(ref_held.shape)
        else:                   # the control in the program's place
            _, ctl_sum = logits(cfg, self.params, x_rows, mm=mm)
            preds = np.repeat(np.asarray(ctl_sum).argmax(1)[:, None],
                              self.n_live, axis=1)
            cached = np.asarray(logits(cfg, self.params, x_held, mm=mm)[0]
                                ).transpose(1, 0, 2)
        return {"logit_gap": check.logit_gap(preds, ref_sum, cached,
                                             ref_held)}
