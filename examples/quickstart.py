"""Quickstart: the whole repo in one spec -> session -> metrics hop.

  PYTHONPATH=src python examples/quickstart.py

Declare the experiment as an ExperimentSpec (validated eagerly: typo a
dataset/mode/first_layer name and the error lists the registered
options), build a Session, run it.  The RunResult carries final
metrics, the per-round trajectory, a process-stable spec hash, and the
git SHA -- the same record the benches stamp their JSON with.  Runs in
seconds on CPU (it is the CI examples-smoke lane); for the LM
substrate demo see examples/quickstart_lm.py.
"""
from repro.api import ExperimentSpec, build
from repro.compile_cache import setup_compile_cache

setup_compile_cache()
spec = ExperimentSpec(dataset="titanic", mode="devertifl", n_clients=3,
                      rounds=3, epochs=2, seeds=(0,))
result = build(spec).run()

print(f"spec {result.spec_hash}  git {result.git_sha}")
for h in result.history:
    print(f"  round {h['round']}  loss={h['loss']:.3f}  F1={h['f1']:.3f}")
print(f"final: F1={result.metrics['f1']:.3f} "
      f"acc={result.metrics['acc']:.3f} "
      f"({result.timings['steps_per_sec']:.0f} steps/s)")
