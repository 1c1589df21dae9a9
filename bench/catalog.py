"""Finding a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; each is a file of its
own (``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json``),
a configuration names its model family
(``bench/families/<family>.py``), and each per-layer metric is a reader
of its own (``bench/metrics/<metric>.py``).  Adding any of them is
adding a file and an entry: nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent
FAMILIES = BENCH / "families"


def benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def cell(name: str, root: Path = ROOT) -> dict:
    """The cell's entry joined with its configuration, traffic and
    metric entries: ``{"workload", "config", "traffic",
    "end_to_end", "per_layer"}``."""
    spec = benchmark(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    with open(root / conf["file"]) as f:
        config = json.load(f)
    with open(BENCH / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)

    def here(m):
        return name in m.get("workloads", [name])
    return {"workload": w, "config": config, "traffic": traffic,
            "end_to_end": [m for m in spec["end_to_end"] if here(m)],
            "per_layer": [m for m in spec["per_layer"] if here(m)]}


def load(path: Path, module: str):
    """The module kept in the file ``path``, as ``module``."""
    mod_spec = importlib.util.spec_from_file_location(module, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def reader(metric: str):
    """The ``read(ctx)`` function of ``bench/metrics/<metric>.py``."""
    return load(BENCH / "metrics" / f"{metric}.py",
                f"bench_metric_{metric.replace('.', '_')}").read


def family(name: str):
    """The model family module ``<FAMILIES>/<name>.py``: ``rows``,
    ``session``, ``follow``, ``context`` and, for serving cells,
    ``serve_params`` and ``serve_logits`` (``bench/README.md``)."""
    path = FAMILIES / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"no model family {name!r}: {path} does not exist")
    return load(path, f"bench_family_{name.replace('.', '_')}")
