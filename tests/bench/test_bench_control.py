"""The control: the plain reference put in the program's place at a
lower precision than the configurations state (float32 at ``highest``),
at a small size.  The serving cell and bank2 fail the three-pass
bfloat16 ``high`` product; mnist5's first losses cannot tell ``high``
from float32 (PERF.md) and fail the one-pass product of the TPU's
default precision."""
import pytest

from benchcells import small_cell

from bench import check, reference  # noqa: E402
from bench.serve import ServeCell  # noqa: E402
from bench.train import TrainCell  # noqa: E402


@pytest.mark.parametrize("name,mm", [
    ("mnist5.train", reference.matmul_bf16),
    ("bank2.train", reference.matmul_high)])
def test_training_control_is_not_correct(name, mm):
    cell = small_cell(name)
    c = TrainCell(cell, seed=2**31 + 21)
    c.setup()
    limits = {k: cell["config"]["limits"][k]
              for k in ("loss_gap", "change_gap")}
    numbers = c.numbers(mm=mm)
    correct, rows = check.verdict(numbers, limits)
    assert correct is False, rows
    program = c.numbers()
    assert check.verdict(program, limits)[0] is True, program


def test_serving_control_is_not_correct():
    cell = small_cell("mnist5.serve")
    c = ServeCell(cell, seed=2**31 + 22)
    c.setup()
    c.window(0.5)
    limits = {"logit_gap": cell["config"]["limits"]["logit_gap"]}
    numbers = c.numbers(mm=reference.matmul_high)
    assert check.verdict(numbers, limits)[0] is False, numbers
    program = c.numbers()
    assert check.verdict(program, limits)[0] is True, program


def test_the_control_product_is_three_bfloat16_passes():
    import jax
    import jax.numpy as jnp
    k = jax.random.split(jax.random.PRNGKey(0), 2)
    a = jax.random.normal(k[0], (32, 48))
    b = jax.random.normal(k[1], (48, 8))
    exact = reference.matmul_highest(a, b)
    high = reference.matmul_high(a, b)
    one_pass = jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    err_high = float(jnp.abs(high - exact).max() / jnp.abs(exact).max())
    err_one = float(jnp.abs(one_pass - exact).max() / jnp.abs(exact).max())
    assert 0 < err_high < 1e-4 < err_one
