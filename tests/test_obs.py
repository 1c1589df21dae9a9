"""The repro.obs observability subsystem.

Contracts pinned here (docs/ARCHITECTURE.md section 12):

  * obs spec parsing (none/basic/full take no args, unknown names
    raise with options, register_obs extends the registry)
  * obs="none" IS the legacy engine (the protocol leaves the impl
    chain unwrapped) and obs is hash-excluded: every level shares ONE
    spec_hash
  * taps are observation-only: obs="full" runs are BITWISE obs="none"
    runs (params, metrics, history), on the scan and python engines,
    also chained behind a schedule + fault + transform stack -- and
    the recorded series are identical across engines
  * the obs x transform x schedule x count sweep grid compiles ONCE
    (round_traces == 1) with every non-none lane bitwise equal to its
    "none" twin; per-cell series carry the [seeds, rounds, ...] axes
  * SpanTracer nesting/export (Chrome trace-event JSON) round-trips;
    NullTracer records nothing and refuses export; at every obs level
    the spans of Session.run and FederatedServer reach a capturing
    profiler's host timeline, and the device programs carry their
    named scopes (forward and backward) in the lowered HLO
  * the unified Telemetry record surfaces on RunResult.telemetry with
    the legacy ``timings`` dict derived from it; ServeReport.obs
    carries the serving copy and prometheus_text renders a valid
    exposition (monotone cumulative buckets, +Inf == count)
  * a checkpoint's stream stamp refuses cross-obs-level resumes, and
    same-level resumes are bitwise
"""
import json
import re

import numpy as np
import pytest

import jax

from repro.api import ExperimentSpec, ServeRequest, build, \
    split_features
from repro.core.protocol import DeVertiFL, ProtocolConfig, \
    resolve_engine
from repro.core.sweep import SweepConfig, run_padded_cells
from repro.obs import (LATENCY_BUCKETS_S, NullTracer, ObsImpl,
                       SERIES_KEYS, SpanTracer, Telemetry,
                       TELEMETRY_SCHEMA_VERSION, get_obs_plan,
                       metrics_table, obs_names, prometheus_text,
                       register_obs)

TINY = dict(dataset="titanic", n_clients=3, rounds=2, epochs=2,
            seeds=(0,))
# taps chained behind the full engine stack
STACK = dict(schedule="stale_k:1", fault="crash:0.5", transform="int8")


def _leaves_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb))


# ---------------------------------------------------------------------------
# registry + spec parsing
# ---------------------------------------------------------------------------
def test_obs_plan_parsing_and_registry_errors():
    assert get_obs_plan("none").level == 0
    assert get_obs_plan("basic").level == 1
    full = get_obs_plan("full")
    assert full.level == 2 and full.spec == "full"
    assert not full.is_none and get_obs_plan("none").is_none
    assert {"none", "basic", "full"} <= set(obs_names())
    with pytest.raises(ValueError, match="basic"):   # options listed
        get_obs_plan("nope")
    with pytest.raises(ValueError, match="no arguments"):
        get_obs_plan("full:3")
    with pytest.raises(ValueError, match="malformed"):
        get_obs_plan("  ")


def test_register_obs_custom_plan_parses_and_is_refused_in_lanes():
    def make(inner, n_clients, batch_size, width, rounds, args):
        return ObsImpl(get_obs_plan("full"), inner, n_clients,
                       batch_size, width, rounds)

    register_obs("test_tap", make, overwrite=True)
    plan = get_obs_plan("test_tap:7")
    assert plan.custom[0] == "test_tap" and plan.custom[2] == ("7",)
    assert not plan.is_none
    # custom plans provide their own impl; they cannot ride the
    # stacked lane state of a multi-level sweep
    impl = ObsImpl(get_obs_plan("full"), _dummy_inner(), 3, 16, 8,
                   rounds=2)
    with pytest.raises(ValueError, match="custom obs plan"):
        impl.init_state(None, obs=plan)


def _dummy_inner():
    from repro.schedule import LaneScheduleImpl
    return LaneScheduleImpl(0, 3, 16, 8)


# ---------------------------------------------------------------------------
# obs="none" is the legacy engine; obs is hash-excluded
# ---------------------------------------------------------------------------
def test_obs_none_leaves_engine_unwrapped_and_hash_is_shared():
    base = ExperimentSpec(**TINY)
    hashes = {base.replace(obs=o).spec_hash
              for o in ("none", "basic", "full")}
    assert len(hashes) == 1     # an obs level is NOT a new experiment
    pcfg = ProtocolConfig(dataset="titanic", n_clients=3, rounds=2)
    _, impl = resolve_engine(pcfg, *_engine_args(pcfg))
    assert impl is None          # untouched legacy sync path
    _, impl = resolve_engine(pcfg.replace(obs="basic"),
                             *_engine_args(pcfg))
    assert isinstance(impl, ObsImpl)


def _engine_args(pcfg):
    from repro.configs import get_config
    from repro.core.protocol import arch_for
    from repro.models.mlp_model import PaperMLP
    return PaperMLP(get_config(arch_for(pcfg.dataset))), 500


def test_obs_requires_devertifl_mode():
    with pytest.raises(ValueError, match="devertifl"):
        ExperimentSpec(**{**TINY, "mode": "non_federated"},
                       obs="basic")


# ---------------------------------------------------------------------------
# bitwise parity + recorded series
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("extra", [{}, STACK],
                         ids=["sync", "sched+fault+wire"])
def test_obs_full_is_bitwise_none_and_records_series(extra):
    a = build(ExperimentSpec(**TINY, **extra)).run()
    b = build(ExperimentSpec(**TINY, **extra, obs="full")).run()
    assert _leaves_equal(a.params, b.params)
    assert a.metrics == b.metrics
    for ha, hb in zip(a.history, b.history):
        np.testing.assert_array_equal(ha["round_losses"],
                                      hb["round_losses"])
    ser = b.telemetry.series
    assert set(ser) == set(SERIES_KEYS)
    R, n = TINY["rounds"], TINY["n_clients"]
    assert ser["loss"].shape == (R,)
    assert ser["exchange_norm"].shape == (R, n)
    assert ser["grad_norm"].shape == (R, n)
    assert (ser["loss"] > 0).all()
    assert (ser["exchange_norm"] > 0).any()
    assert (ser["grad_norm"] > 0).any()
    if extra:
        assert (ser["staleness"] == 1).all()
        assert (ser["encoded_bytes"] > 0).all()
    # the obs-free run records nothing but keeps the unified record
    assert a.telemetry.series is None
    assert a.timings == a.telemetry.to_timings()


def test_obs_basic_skips_per_client_series():
    res = build(ExperimentSpec(**TINY, obs="basic")).run()
    ser = res.telemetry.series
    assert (ser["loss"] > 0).all()
    # basic never traces the norm taps (static level bound): the
    # per-client series stay exact zeros
    assert (ser["exchange_norm"] == 0).all()
    assert (ser["grad_norm"] == 0).all()


def test_obs_series_identical_across_scan_and_python_engines():
    a = build(ExperimentSpec(**TINY, **STACK, obs="full")).run()
    b = build(ExperimentSpec(**TINY, **STACK, obs="full",
                             engine="python")).run()
    assert _leaves_equal(a.params, b.params)
    for k in SERIES_KEYS:
        np.testing.assert_array_equal(a.telemetry.series[k],
                                      b.telemetry.series[k])


# ---------------------------------------------------------------------------
# sweep lanes: one compile, none-lane parity, per-cell series
# ---------------------------------------------------------------------------
def test_obs_grid_compiles_once_with_none_lanes_bitwise():
    scfg = SweepConfig(datasets=("titanic",), modes=("devertifl",),
                       client_counts=(2, 3), seeds=(0,), rounds=2,
                       epochs=1, schedules=("sync", "stale_k:1"),
                       transforms=("none", "int8"),
                       obs=("none", "basic", "full"))
    out = run_padded_cells("titanic", "devertifl", scfg)
    assert out["round_traces"] == 1
    assert out["obs"] == ["none", "basic", "full"]
    cells = out["cells"]
    assert len(cells) == 3 * 2 * 2 * 2
    for key, cell in cells.items():
        level = key.split("/")[0]
        assert cell["obs"] == level
        if level == "none":
            continue
        twin = cells["none/" + key.split("/", 1)[1]]
        assert cell["acc_per_seed"] == twin["acc_per_seed"]
        assert cell["f1_per_seed"] == twin["f1_per_seed"]
        ser = cell["obs_series"]
        # leading seed axis, then rounds (and the padded client axis)
        assert ser["loss"].shape == (1, 2)
        assert ser["exchange_norm"].shape == (1, 2, 3)
        if level == "full":
            assert (ser["grad_norm"] > 0).any()
        else:
            assert (ser["grad_norm"] == 0).all()


def test_obs_sweep_refuses_custom_plans_and_non_devertifl():
    register_obs("test_tap2", lambda **kw: None, overwrite=True)
    scfg = SweepConfig(datasets=("titanic",), modes=("devertifl",),
                       client_counts=(2,), seeds=(0,), rounds=1,
                       epochs=1, obs=("none", "test_tap2"))
    with pytest.raises(ValueError, match="custom obs"):
        run_padded_cells("titanic", "devertifl", scfg)
    scfg2 = scfg.__class__(**{**scfg.__dict__,
                              "modes": ("verticomb",),
                              "obs": ("basic",)})
    with pytest.raises(ValueError, match="devertifl"):
        run_padded_cells("titanic", "verticomb", scfg2)


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------
def test_span_tracer_nesting_export_and_summary(tmp_path):
    tr = SpanTracer()
    assert tr.active
    with tr.span("outer", cat="t"):
        with tr.span("inner", cat="t", round=1):
            with tr.span("leaf", cat="t", x=2):
                pass
    recs = tr.to_records()
    by = {r["name"]: r for r in recs}
    assert by["outer"]["depth"] == 0 and by["inner"]["depth"] == 1
    assert by["leaf"]["depth"] == 2
    assert by["inner"]["args"]["round"] == 1
    assert by["leaf"]["args"]["x"] == 2
    assert by["outer"]["dur"] >= by["inner"]["dur"] >= \
        by["leaf"]["dur"] >= 0
    assert not hasattr(tr, "instant")
    path = tr.export(str(tmp_path / "trace.json"))
    doc = json.load(open(path))
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert {e["ph"] for e in evs} == {"X"}
    for e in evs:                       # Perfetto-required fields
        assert {"name", "ph", "ts", "dur", "pid", "tid"} <= set(e)
    text = tr.summary()
    assert "outer" in text and "inner" in text


def test_null_tracer_is_inert_and_refuses_export():
    tr = NullTracer()
    assert not tr.active
    with tr.span("x", round=1):
        with tr.span("y"):
            pass
    assert tr.to_records() == []
    assert not hasattr(tr, "instant")
    with pytest.raises(ValueError, match="obs"):
        tr.export("/tmp/never.json")


def test_session_tracer_spans_cover_the_run(tmp_path):
    sess = build(ExperimentSpec(**TINY, obs="basic"))
    sess.run()
    recs = sess.tracer.to_records()
    names = [r["name"] for r in recs]
    assert names.count("round") == TINY["rounds"]
    assert "build" in names and "eval" in names
    path = sess.tracer.export(str(tmp_path / "t.json"))
    assert json.load(open(path))["traceEvents"]
    # obs="none" sessions carry the no-op tracer
    assert not build(ExperimentSpec(**TINY)).tracer.active


def _profiled_spans(directory):
    """The ``devertifl.*`` event names on the host planes of the
    profile written under ``directory``."""
    import glob
    import os
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    data = ProfileData.from_file(path)
    return [e.name for p in data.planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events
            if e.name.startswith("devertifl.")]


def test_spans_reach_the_profiler_at_obs_none(tmp_path):
    sess = build(ExperimentSpec(**TINY))
    with jax.profiler.trace(str(tmp_path / "run")):
        res = sess.run()
    names = _profiled_spans(str(tmp_path / "run"))
    evals = TINY["rounds"] + 1                  # every round + final
    assert names.count("devertifl.run") == 1
    assert names.count("devertifl.init") == 1
    assert names.count("devertifl.round") == TINY["rounds"]
    for name in ("eval", "predict", "score"):
        assert names.count("devertifl." + name) == evals
    srv = sess.server(max_slots=2)
    xte = np.asarray(sess.federation.xte)
    with jax.profiler.trace(str(tmp_path / "serve")):
        for i in range(3):
            srv.submit(ServeRequest(uid=i, slices=split_features(
                sess.federation.layout, xte[i])))
        srv.run()
    names = _profiled_spans(str(tmp_path / "serve"))
    assert names.count("devertifl.submit") == 3
    assert names.count("devertifl.offer") == 3 * TINY["n_clients"]
    assert names.count("devertifl.step") >= 2
    for name in ("admit", "upload", "fetch", "complete"):
        assert "devertifl." + name in names
    # the in-memory record stays off at obs="none"
    assert sess.tracer.to_records() == []
    assert res.telemetry.spans is None


SCOPES = ("batch", "first_layer", "tower", "exchange", "loss",
          "optimizer", "fedavg", "wire", "guard", "taps")


def _scope_forms(lowered):
    """scope -> the path components naming it in the lowered program's
    locations (``first_layer``, ``jvp(first_layer)``, ...), and the
    locations that name more than one scope."""
    forms, both = {}, []
    for loc in re.findall(r'loc\("([^"]*)"',
                          lowered.as_text(debug_info=True)):
        hit = set()
        for part in loc.split("/"):
            bare = part
            while bare.endswith(")") and "(" in bare:
                bare = bare[bare.index("(") + 1:-1]
            if bare in SCOPES:
                forms.setdefault(bare, set()).add(part)
                hit.add(bare)
        if len(hit) > 1:
            both.append(loc)
    return forms, both


@pytest.mark.parametrize("first_layer", ["slice", "pallas"])
@pytest.mark.parametrize("extra", [{}, {**STACK, "obs": "full"}],
                         ids=["sync", "stack"])
def test_device_programs_carry_named_scopes(first_layer, extra):
    from repro.core.protocol import train_keys
    from repro.serving.federated import FederatedServer
    fed = build(ExperimentSpec(**TINY, first_layer=first_layer,
                               **extra)).federation
    init_key, loop_key = train_keys(jax.random.PRNGKey(0))
    params = fed.init_params(init_key)
    forms, both = _scope_forms(fed._round.lower(
        params, jax.vmap(fed.opt.init)(params),
        jax.numpy.zeros((), jax.numpy.int32), fed.init_sched_state(),
        loop_key, fed._xtr, fed._ytr, fed._lay))
    assert not both                      # no op is in two scopes
    # forward and backward of the differentiated layers
    for scope in ("first_layer", "tower", "loss"):
        assert {f"jvp({scope})", f"transpose(jvp({scope}))"} <= \
            forms[scope]
    assert "jvp(exchange)" in forms["exchange"]
    for scope in ("batch", "optimizer", "fedavg"):
        assert forms[scope] == {scope}
    machinery = {"wire", "guard", "taps"}
    assert machinery <= set(forms) if extra else \
        not machinery & set(forms)
    # the inference programs: predict and the serving slot step
    forms, both = _scope_forms(fed._predict.lower(params, fed._xte,
                                                  fed._lay))
    assert not both
    assert {"first_layer", "tower", "exchange"} <= set(forms)
    srv = FederatedServer(fed.model, fed.pcfg, fed.layout, params,
                          max_slots=2)
    forms, both = _scope_forms(srv._step_fn.lower(
        params, srv._xbuf, srv._hbuf, srv._ubuf, srv._mbuf, srv._lay))
    assert not both
    assert {"first_layer", "tower", "exchange"} <= set(forms)
    assert ("wire" in forms) == bool(extra)


# ---------------------------------------------------------------------------
# unified telemetry record
# ---------------------------------------------------------------------------
def test_telemetry_record_and_legacy_timings_alias():
    res = build(ExperimentSpec(**TINY, **STACK, obs="full")).run()
    tel = res.telemetry
    assert tel.schema_version == TELEMETRY_SCHEMA_VERSION
    assert res.schema_version == 5
    assert res.timings == tel.to_timings()
    assert res.timings["fault"] == tel.fault
    assert res.timings["wire"] == tel.wire
    d = res.to_dict()
    json.dumps(d)                        # JSON-safe end to end
    assert d["telemetry"]["series"]["loss"] == \
        list(tel.series["loss"])
    # custom runners lift legacy dicts into the record
    lifted = Telemetry.from_timings({"wall_s": 2.0, "fault": {"x": 1}})
    assert lifted.wall_s == 2.0 and lifted.fault == {"x": 1}
    assert "obs=" not in metrics_table(res)      # renders, no crash
    assert "steps/sec" in metrics_table(res)


# ---------------------------------------------------------------------------
# serving: ServeReport.obs + prometheus exposition
# ---------------------------------------------------------------------------
_LINE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
                   r'(\{[a-zA-Z_]+="[^"]*"(,[a-zA-Z_]+="[^"]*")*\})?'
                   r" -?[0-9.e+Inf-]+$")


@pytest.fixture(scope="module")
def served():
    spec = ExperimentSpec(dataset="titanic", n_clients=3, rounds=1,
                          epochs=1, seeds=(0,), eval_every=0,
                          obs="basic")
    sess = build(spec)
    sess.run()
    lay = sess.federation.layout
    xte = np.asarray(sess.federation.xte)
    reqs = [ServeRequest(uid=f"u{i}", entity_id=f"e{i}",
                         slices=split_features(lay, xte[i]))
            for i in range(6)]
    return sess, sess.serve(reqs, max_slots=3)


def test_serve_report_carries_unified_obs_record(served):
    sess, rep = served
    assert rep.schema_version == 2
    obs = rep.obs
    assert obs["schema_version"] == TELEMETRY_SCHEMA_VERSION
    assert obs["serve"]["submitted"] == rep.counters["submitted"]
    assert obs["serve"]["completed"] == rep.counters["completed"]
    assert obs["serve"]["throughput_rps"] == rep.throughput_rps
    json.dumps(rep.to_dict())
    # the server's spans show up on the session tracer: one submit and
    # one offer a call, and each step with its four parts inside
    recs = sess.tracer.to_records()
    names = [r["name"] for r in recs]
    assert names.count("submit") == rep.counters["submitted"]
    assert names.count("offer") == rep.counters["submitted"] * 3
    assert names.count("step") >= rep.counters["steps"]
    for part in ("admit", "upload", "fetch", "complete"):
        assert part in names
    depth = {r["name"]: r["depth"] for r in recs}
    assert depth["admit"] == depth["fetch"] == depth["step"] + 1
    assert all(r["args"] == {} for r in recs if r["cat"] == "serve")


def test_prometheus_text_is_a_valid_exposition(served):
    _, rep = served
    text = prometheus_text(rep)
    assert text.endswith("\n")
    for ln in text.splitlines():
        if ln.startswith("#"):
            assert re.match(r"^# (HELP|TYPE) repro_serve_\w+ ", ln)
        else:
            assert _LINE.match(ln), ln
    assert f"repro_serve_submitted_total {rep.counters['submitted']}" \
        in text
    # cumulative latency histogram: monotone, +Inf equals _count
    buckets = re.findall(
        r'repro_serve_latency_seconds_bucket\{le="([^"]+)"\} (\d+)',
        text)
    assert buckets[-1][0] == "+Inf"
    assert [float(b[0]) for b in buckets[:-1]] == \
        list(LATENCY_BUCKETS_S)
    counts = [int(b[1]) for b in buckets]
    assert counts == sorted(counts)
    total = int(re.search(
        r"repro_serve_latency_seconds_count (\d+)", text).group(1))
    assert counts[-1] == total == rep.counters["completed"]


# ---------------------------------------------------------------------------
# checkpoint stream stamp
# ---------------------------------------------------------------------------
def test_obs_checkpoint_stamp_refuses_cross_level_resume(tmp_path):
    d = str(tmp_path / "ckpt")
    kw = dict(dataset="titanic", n_clients=3, epochs=1, seeds=(0,),
              obs="basic")
    full = build(ExperimentSpec(rounds=4, **kw)).run()
    build(ExperimentSpec(rounds=2, checkpoint_dir=d,
                         checkpoint_every=1, **kw)).run()
    res = build(ExperimentSpec(rounds=4, checkpoint_dir=d,
                               checkpoint_every=1, **kw)).resume()
    assert res.resumed_from == 2
    assert res.metrics == full.metrics
    with pytest.raises(ValueError, match="or obs level"):
        build(ExperimentSpec(rounds=4, checkpoint_dir=d,
                             checkpoint_every=1,
                             **{**kw, "obs": "full"})).resume()


def test_obs_free_checkpoints_refuse_obs_resume(tmp_path):
    """An obs-free checkpoint has no series buffers to restore: the
    stream stamp (sync vs sync|obs=basic) refuses the splice."""
    d = str(tmp_path / "ckpt")
    kw = dict(dataset="titanic", n_clients=3, epochs=1, seeds=(0,))
    build(ExperimentSpec(rounds=2, checkpoint_dir=d,
                         checkpoint_every=1, **kw)).run()
    with pytest.raises(ValueError, match="or obs level"):
        build(ExperimentSpec(rounds=4, checkpoint_dir=d,
                             checkpoint_every=1, obs="basic",
                             **kw)).resume()
