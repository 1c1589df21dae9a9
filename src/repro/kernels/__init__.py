# Pallas TPU kernels for the compute hot spots:
#   vfl_matmul      -- block-sparse first-layer matmul implementing the
#                      paper's zero-padding without multiplying zeros
#   flash_attention -- causal/SWA/GQA/softcap flash attention
#   rwkv6_scan      -- RWKV6 WKV recurrence (data-dependent decay)
# Each package: kernel (pl.pallas_call + BlockSpec), ops.py (jit'd
# wrapper), ref.py (pure-jnp oracle). Each wrapper's interpret=None
# resolves through interpret_default: the compiled kernel on a TPU, the
# Pallas interpreter elsewhere (the CPU tests).
import jax


def interpret_default() -> bool:
    """Whether a kernel called without ``interpret=`` runs in the
    Pallas interpreter: everywhere but on a TPU."""
    return jax.default_backend() != "tpu"
