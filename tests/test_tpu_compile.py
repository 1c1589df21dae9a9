"""Compile the Pallas first layer for a described TPU v5e chip.

Interpret mode (the rest of the CPU suite) cannot see what the chip's
compiler refuses: block shapes off the (8, 128) tiling, too much VMEM.
These tests hand the compiled kernel (``interpret=False``) to the TPU
compiler installed with jax, for a chip that is described, not
attached, at the layouts the main path builds: mnist with 2, 3 and 5
clients and bank with 3 (17-column slices) at batch 64.  Nothing runs;
a compile that passes is not a chip run.

The topology is described inside a fixture, never at import, so every
pytest-xdist worker collects the same tests and only the worker that
runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import partition as PT
from repro.core.protocol import ProtocolConfig, arch_for, make_first_layer_fn
from repro.kernels.vfl_matmul import vfl_matmul
from repro.models.mlp_model import PaperMLP

LAYOUTS = [("mnist", 2), ("mnist", 3), ("mnist", 5), ("bank", 3)]
BATCH = 64


@pytest.fixture(scope="module")
def one_chip():
    """One described v5e chip, with the persistent compilation cache
    off: a compile for a described chip is written to the cache but
    cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _federation(dataset, n_clients):
    model = PaperMLP(get_config(arch_for(dataset)))
    layout = PT.make_layout(dataset, model.in_features, n_clients, seed=0)
    return model, layout


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("dataset,n_clients", LAYOUTS)
def test_vfl_matmul_fwd_vjp_compiles_for_v5e(one_chip, dataset, n_clients):
    model, layout = _federation(dataset, n_clients)
    F, H = model.in_features, model.hidden
    for off, k in zip(layout.offsets, layout.sizes):
        def fwd_vjp(x, w, g, off=off):
            y, vjp = jax.vjp(
                lambda x, w: vfl_matmul(x, w, off, interpret=False), x, w)
            return y, vjp(g)

        args = _on(one_chip, (jax.ShapeDtypeStruct((BATCH, k), jnp.float32),
                              jax.ShapeDtypeStruct((F, H), jnp.float32),
                              jax.ShapeDtypeStruct((BATCH, H), jnp.float32)))
        hlo = jax.jit(fwd_vjp).lower(*args).compile().as_text()
        assert "tpu_custom_call" in hlo, (dataset, n_clients, off, k)


@pytest.mark.parametrize("dataset,n_clients", LAYOUTS)
def test_first_pallas_layer_compiles_for_v5e(one_chip, dataset, n_clients):
    model, layout = _federation(dataset, n_clients)
    pcfg = ProtocolConfig(dataset=dataset, n_clients=n_clients,
                          first_layer="pallas")
    first = make_first_layer_fn(model, pcfg, layout, interpret=False)
    params = jax.eval_shape(
        lambda k: jax.vmap(model.init)(jax.random.split(k, n_clients)),
        jax.random.PRNGKey(0))
    args = _on(one_chip, (
        params, jax.ShapeDtypeStruct((BATCH, model.in_features), jnp.float32),
        jax.eval_shape(layout.arrays)))

    def loss(p, xb, lay):
        return (first(p, xb, lay) ** 2).sum()

    hlo = jax.jit(jax.value_and_grad(loss)).lower(*args).compile().as_text()
    # one kernel per live client: none fell back to interpret mode
    assert hlo.count("tpu_custom_call") >= n_clients, (dataset, n_clients)
