"""Model families as files: a configuration's ``model.family`` names
the file (``bench/families/<family>.py``) that brings its rows, its
session, its reference and its work counts, and the generic harness
reads nothing of the model itself."""
import json
import re
from pathlib import Path

import pytest

from benchcells import ROOT, small_cell

from bench import catalog, check, peaks, work  # noqa: E402
from bench.run import run_context  # noqa: E402
from bench.serve import ServeCell  # noqa: E402
from bench.train import TrainCell  # noqa: E402

ELSEWHERE = Path(__file__).resolve().parent / "families"


def test_a_family_kept_elsewhere_drives_a_train_cell(monkeypatch):
    monkeypatch.setattr(catalog, "FAMILIES", ELSEWHERE)
    cell = small_cell("mnist5.train")
    cell["config"]["model"]["family"] = "tower"
    job = TrainCell(cell, seed=2**31 + 41)
    assert Path(job.family.__file__) == ELSEWHERE / "tower.py"
    job.setup()
    rate = job.window(0.2)["train_samples_per_s"]
    peak = peaks.peak("TPU v5 lite")
    ctx = run_context(job, cell, peak)
    mfu = catalog.reader("train_mfu")(ctx)
    assert mfu == pytest.approx(100.0 * 40360 * rate
                                / peak["bf16_flops_per_s"])
    limits = {k: cell["config"]["limits"][k]
              for k in ("loss_gap", "change_gap")}
    correct, rows = check.verdict(job.numbers(), limits)
    assert correct is True, rows


@pytest.mark.parametrize("name,job", [("mnist5.train", TrainCell),
                                      ("mnist5.serve", ServeCell)])
def test_a_family_with_no_file_fails_naming_the_path(name, job):
    cell = small_cell(name)
    cell["config"]["model"]["family"] = "nosuch"
    missing = Path(ROOT) / "bench" / "families" / "nosuch.py"
    with pytest.raises(SystemExit, match=re.escape(str(missing))):
        job(cell, seed=1)


def test_a_family_without_serving_functions_cannot_serve(tmp_path,
                                                         monkeypatch):
    (tmp_path / "trainonly.py").write_text(
        "from bench.families.mlp import context, follow, rows, session"
        "  # noqa: F401\n")
    monkeypatch.setattr(catalog, "FAMILIES", tmp_path)
    cell = small_cell("mnist5.serve")
    cell["config"]["model"]["family"] = "trainonly"
    with pytest.raises(SystemExit, match="no serve_params or "
                       "serve_logits: it cannot serve"):
        ServeCell(cell, seed=1)


@pytest.mark.parametrize("config,widths,n_classes,flops", [
    # image rows dealt to 5 clients: 6, 6, 6, 5, 5 rows of 28 pixels.
    # forward first 2*10*784, rest 5 * (2*10*10*2 + 2*10*10);
    # backward the first's weights once more, the rest twice
    ("mnist5", [168, 168, 168, 140, 140], 10,
     15680 + 3000 + 15680 + 6000),
    # columns dealt to 2 clients: 26 and 25.
    # forward first 2*10*51, rest 2 * (2*10*10*2 + 2*10*2)
    ("bank2", [26, 25], 2, 1020 + 880 + 1020 + 1760)])
def test_mlp_context_counts_the_step_as_work_does(config, widths,
                                                  n_classes, flops):
    with open(Path(ROOT) / "bench" / "configs" / f"{config}.json") as f:
        cfg = json.load(f)
    ctx = catalog.family(cfg["model"]["family"]).context(cfg)
    assert ctx["widths"] == widths
    assert (ctx["hidden"], ctx["n_hidden"], ctx["n_classes"]) == \
        (10, 3, n_classes)
    assert ctx["step_flops_per_sample"] == \
        work.step_flops_per_sample(widths, 10, 3, n_classes) == flops


def test_the_generic_harness_reads_no_model_shape():
    # what depends on the architecture lives in bench/families/, the
    # reference and the work counts; the rest reads the family
    shape = re.compile(
        r"""\[["'](hidden|n_hidden|in_features|n_classes|partition)["']\]"""
        r"|reference\.(dims|partition|canonical|train|serve_logits)\(")
    generic = [p for p in sorted((Path(ROOT) / "bench").glob("*.py"))
               if p.name not in ("reference.py", "work.py")]
    assert len(generic) > 10
    found = {p.name: shape.findall(p.read_text()) for p in generic}
    assert not any(found.values()), found
