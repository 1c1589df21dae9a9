"""A model family kept outside ``bench/families/``: the MLP family under
another name, for the tests that load a configuration's family from a
file of its own."""
from bench.families.mlp import (  # noqa: F401
    context, follow, rows, serve_logits, serve_params, session)
