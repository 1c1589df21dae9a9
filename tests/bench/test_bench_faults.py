"""The rest of a benchmark run, with the look for a chip skipped, at a
small size on the CPU: a sound run comes out correct, and a run whose
timed path is broken underneath comes out not correct -- once for each
fault the cells can have.  No cell runs on several chips; the exchange
left out is the one between the federation's parties, FedAvg."""
import json

import jax
import pytest

import benchcells
from benchcells import small_cell

import bench.run as run  # noqa: E402
from bench import catalog, readings  # noqa: E402

SAVED = ("jax_compilation_cache_dir",
         "jax_persistent_cache_min_compile_time_secs",
         "jax_persistent_cache_min_entry_size_bytes",
         "jax_default_matmul_precision")


@pytest.fixture
def bench_run(monkeypatch, tmp_path, capsys):
    """Run ``bench/run.py``'s main at the small size; returns its
    result line."""
    saved = {k: getattr(jax.config, k) for k in SAVED}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(catalog, "cell", small_cell)
    monkeypatch.setattr(run, "device_record", lambda chips: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": 1})

    def go(workload, trace=0):
        capsys.readouterr()
        run.main(["--workload", workload, "--seed", str(2**31 + 11),
                  "--seconds", "0.5", "--trace", str(trace)])
        return json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    yield go
    for k, v in saved.items():
        jax.config.update(k, v)


def state_unchanged(monkeypatch):
    """Every optimizer step hands back the parameters it was given."""
    return readings.state_unchanged()


def half_batch(monkeypatch):
    """The loss takes the mean over half of each batch only."""
    return readings.half_batch()


def fedavg_skipped(monkeypatch):
    """Each round ends with every party's parameters unaveraged."""
    return readings.fedavg_skipped()


def answer_altered(monkeypatch):
    """The serving step's classes come out shifted by one."""
    from repro.serving import federated
    make = federated.make_serve_step_fn

    def altered(model, pcfg, layout, first_layer_fn=None):
        step = make(model, pcfg, layout, first_layer_fn)

        def shifted(*args):
            preds, h_all = step(*args)
            return (preds + 1) % model.n_classes, h_all
        return shifted
    monkeypatch.setattr(federated, "make_serve_step_fn", altered)
    return lambda: None


@pytest.mark.parametrize("workload", ["mnist5.train", "bank2.train",
                                      "mnist5.serve"])
def test_sound_run_is_correct(bench_run, workload):
    out = bench_run(workload)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert "setup_s" in out["metrics"]
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("workload,fault", [
    ("mnist5.train", state_unchanged), ("mnist5.train", half_batch),
    ("mnist5.train", fedavg_skipped),
    ("bank2.train", state_unchanged), ("bank2.train", half_batch),
    ("bank2.train", fedavg_skipped),
    ("mnist5.serve", answer_altered)])
def test_broken_timed_path_is_not_correct(bench_run, monkeypatch,
                                          workload, fault):
    undo = fault(monkeypatch)
    try:
        out = bench_run(workload)
    finally:
        undo()
    assert out["correct"] is False, out["checks"]


def test_traced_run_reports_per_layer_metrics_it_can_read(bench_run):
    # on the CPU there is no device plane: the trace readers find
    # nothing and leave their metrics out; the counters still read
    out = bench_run("mnist5.serve", trace=1)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"serve_host_us_per_req",
                                   "serve_cache_hit_rate"}
    assert "breakdown" in out and "window_s" in out["device"]


def test_benchcells_keep_the_cells_shapes():
    # the small cells cut rows only: widths, clients and traffic kind
    # are the benchmark's own
    for name in ("mnist5.train", "bank2.train", "mnist5.serve"):
        small, full = benchcells.small_cell(name), benchcells.CELL(name)
        assert small["config"]["model"] == full["config"]["model"]
        assert small["config"]["federation"]["n_clients"] == \
            full["config"]["federation"]["n_clients"]
        assert small["traffic"]["kind"] == full["traffic"]["kind"]
