"""Training traffic: whole ``Session.run`` calls of the configuration's
federation, as many as fill the window.

The traffic file gives ``rounds_per_call`` and ``eval_every``.  Set-up
builds one Session, and its first call, which compiles every program
the window runs, drives the federation from the seed; the window then
repeats the same call on the same Session.  Each call initialises from
the seed, trains its rounds (with the per-round eval the traffic asks
for) and evaluates the result, as a user's ``Session.run`` does.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from bench import catalog, check


class TrainCell:
    def __init__(self, cell: dict, seed: int):
        self.config, self.traffic = cell["config"], cell["traffic"]
        self.seed = seed
        self.family = catalog.family(self.config["model"]["family"])

    # ------------------------------------------------------------------
    def setup(self):
        """Build the Session and drive its first call from the seed."""
        cfg, trf = self.config, self.traffic
        self.arrays = jax.block_until_ready(
            self.family.rows(cfg, self.seed))
        self.marks = [("data", time.perf_counter())]
        self.sess = self.family.session(cfg, self.arrays, self.seed,
                                        rounds=trf["rounds_per_call"],
                                        eval_every=trf["eval_every"])
        fed = self.sess.federation
        self.marks.append(("session", time.perf_counter()))
        self.rounds = trf["rounds_per_call"]
        self.steps_per_round = fed._steps_per_round
        self.batch = fed.bs
        first = self.sess.run()
        self.first_losses = np.asarray(first.history[0]["round_losses"]
                                       if first.history else [])
        self.first_params = jax.device_get(first.params)
        self.marks.append(("first call", time.perf_counter()))
        self.samples_per_call = (self.rounds * self.steps_per_round
                                 * self.batch)
        self.attempted = self.failed = 0

    def _call(self):
        res = self.sess.run()
        self.attempted += self.rounds
        self.failed += sum(not np.all(np.isfinite(h["round_losses"]))
                           for h in res.history)
        if not np.isfinite(res.metrics["f1"]):
            self.failed += 1

    def window(self, seconds: float) -> dict:
        """Whole calls until ``seconds`` have passed; the rate is every
        row trained over all of that time."""
        calls = 0
        t0 = time.perf_counter()
        while True:
            self._call()
            calls += 1
            if time.perf_counter() - t0 >= seconds:
                break
        wall = time.perf_counter() - t0
        self.window_calls, self.window_wall = calls, wall
        self.samples_per_s = calls * self.samples_per_call / wall
        return {"train_samples_per_s": self.samples_per_s}

    def traced(self):
        """The work of the traced window: one more call."""
        self._call()
        return {"steps": self.rounds * self.steps_per_round}

    def notes(self):
        return [f"window: {self.window_calls} calls of {self.rounds} "
                f"rounds x {self.steps_per_round} steps in "
                f"{self.window_wall:.3f} s"]

    def release(self):
        """Free the program's state before the reference runs."""
        self.sess = None

    # ------------------------------------------------------------------
    def follow(self, rounds, mm=None, device=None):
        """The reference (or, with a lower product ``mm``, the control)
        from the seed through ``rounds`` rounds of this cell's training
        rows (the model family's ``follow``)."""
        return self.family.follow(self.config, self.arrays, self.seed,
                                  rounds, mm=mm, device=device)

    def numbers(self, mm=None):
        """The compared numbers of the set-up call against the
        reference's whole call: ``loss_gap`` of the first steps and
        ``change_gap`` of every round (``bench.check``).  With ``mm``
        the reference at that product stands in for the program (the
        control)."""
        ref = self.follow(self.rounds)
        if mm is None:
            losses, final = self.first_losses, self.first_params
        else:
            ctl = self.follow(self.rounds, mm=mm)
            losses, final = ctl["losses"], ctl["final"]
        change, _ = check.change_gap(final, ref["start"], ref["final"],
                                     ref["first_grads"])
        return {"loss_gap": check.loss_gap(losses, ref["losses"],
                                           check.CHECK_STEPS),
                "change_gap": change}
