"""The plain reference against the program at a small size on the CPU,
where float32 matmuls are exact float32: ``Session.run``'s losses and
parameters, and ``Session.predict``'s classes."""
import numpy as np
import pytest

from benchcells import small_cell

import jax  # noqa: E402
from bench import catalog, reference  # noqa: E402
from bench.train import TrainCell  # noqa: E402


@pytest.mark.parametrize("name", ["mnist5.train", "bank2.train"])
def test_reference_follows_session_run(name):
    c = TrainCell(small_cell(name), seed=2**31 + 7)
    c.setup()
    ref = c.follow(c.rounds)
    np.testing.assert_allclose(c.first_losses, ref["losses"], rtol=1e-5)
    for got, want in zip(jax.tree.leaves(c.first_params),
                         jax.tree.leaves(ref["final"])):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
    assert c.numbers()["loss_gap"] < 1e-5
    # not trivially close: another seed's reference is far
    c.seed += 1
    assert c.numbers()["loss_gap"] > 1e-3


def test_reference_forward_matches_predict():
    mlp = catalog.family("mlp")
    cell = small_cell("mnist5.serve")
    cfg = cell["config"]
    arrays = mlp.rows(cfg, 3)
    sess = mlp.session(cfg, arrays, 3, rounds=1, eval_every=0)
    params = mlp.serve_params(cfg, jax.random.PRNGKey(3))
    xte = np.asarray(arrays[2])
    got = np.asarray(sess.predict(xte, params=params))      # [n, B]
    order, slices = reference.canonical(reference.partition(
        "image_rows", 784, 5))
    stack, total = reference.serve_logits(params, xte[:, order],
                                          slices=slices)
    total = np.asarray(total)
    want = total.argmax(1)
    assert np.array_equal(got, np.broadcast_to(want, got.shape))
    # the program's per-client logits are the reference's stack
    from repro.core.protocol import make_h_all_fn
    fed = sess.federation
    h = jax.jit(make_h_all_fn(fed.model, fed.pcfg, layout=fed.layout))(
        params, fed.layout.apply(xte), fed._lay)
    np.testing.assert_allclose(np.asarray(h), np.asarray(stack),
                               rtol=1e-5, atol=1e-5)
