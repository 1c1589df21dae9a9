"""The readers of the program's own names -- host spans and the named
scopes of the round program -- on small made-up traces, and the map
from instruction names to name stacks on programs compiled here (no
chip, no libtpu)."""
import jax
import jax.numpy as jnp
import pytest

from benchcells import small_cell

from bench import catalog, scopes, trace  # noqa: E402
from bench.trace import Event  # noqa: E402


def ev(name, start, end):
    return Event(name, float(start), float(end))


ROUND = "jit(round_fn)/while/body"
NAMES = {"fusion.1": f"{ROUND}/jvp(first_layer)/dot_general",
         "copy.2": f"{ROUND}/transpose(jvp(first_layer))/transpose",
         "fusion.3": f"{ROUND}/optimizer/mul",
         "fusion.4": f"{ROUND}/jvp(exchange)/reduce_sum",
         "fusion.5": "jit(round_fn)/fedavg/reduce_sum",
         "fusion.6": f"{ROUND}/jvp(first_layer_norm)/add",
         "while.7": f"{ROUND}/first_layer",
         "copy.8": "jit(round_fn)/while"}


def round_trace():
    """Two chips, each running two rounds of two steps; every op names
    an instruction of NAMES."""
    ops, modules = {}, {}
    for c, scale in ((0, 1), (1, 3)):
        evs, mods = [], []
        for r in range(2):
            t = 1000 * r
            mods.append(ev("jit_round_fn(4)", t, t + 900))
            evs.append(ev("while.7", t, t + 800))       # container
            for s in range(2):
                u = t + 400 * s
                evs += [ev("fusion.1", u, u + 10 * scale),
                        ev("copy.2", u + 10 * scale, u + 30 * scale),
                        ev("fusion.3", u + 100, u + 105),
                        ev("fusion.4", u + 110, u + 112),
                        ev("fusion.6", u + 120, u + 150),
                        ev("copy.8", u + 200, u + 240)]
            evs.append(ev("fusion.5", t + 850, t + 870))
        mods.append(ev("jit_predict(9)", 5000, 6000))
        evs.append(ev("fusion.1", 5100, 5900))          # not in a round
        ops[c], modules[c] = evs, mods
    return trace.Trace(ops=ops, modules=modules, window=(0.0, 7000.0))


@pytest.fixture
def mapped(monkeypatch):
    """The readers' map from instructions to name stacks, as
    ``round_stacks`` would read it from the compiled round program."""
    monkeypatch.setattr(scopes, "round_stacks", lambda ctx: NAMES)


@pytest.mark.parametrize("stack,scope,inside", [
    ("jit(f)/first_layer/dot_general", "first_layer", True),
    ("jit(f)/jvp(first_layer)/dot_general", "first_layer", True),
    ("jit(f)/transpose(jvp(first_layer))/dot_general", "first_layer",
     True),
    ("jit(f)/while/body/closed_call/optimizer/mul", "optimizer", True),
    ("jit(f)/jvp(first_layer_norm)/add", "first_layer", False),
    ("jit(f)/my_first_layer/add", "first_layer", False),
    ("jit(f)/first_layer.1/add", "first_layer", False),
    ("jit(f)/while", "first_layer", False),
    ("", "first_layer", False)])
def test_scope_is_a_whole_component_bare_or_wrapped(stack, scope, inside):
    assert scopes.in_scope(stack, scope) is inside


def test_scoped_leaves_container_ops_out():
    evs = round_trace().ops[0]
    got = scopes.scoped(evs, NAMES, "first_layer")
    assert {e.name for e in got} == {"fusion.1", "copy.2"}
    assert not any(trace.CONTAINERS.match(e.name) for e in got)


def test_device_time_by_scope_inside_the_round_program():
    tr = round_trace()
    # chip 0: (10 + 20) ns a step, chip 1: (30 + 60); 4 steps each;
    # the predict program's fusion.1 is left out
    assert scopes.device_ns(tr, NAMES, "first_layer") == \
        pytest.approx((120 + 360) / 2)
    assert scopes.device_ns(tr, NAMES, "fedavg") == pytest.approx(40)
    assert scopes.device_ns(tr, NAMES, "tower") is None
    assert scopes.device_ns(tr, {}, "first_layer") is None
    assert scopes.rounds(tr) == 2


def test_scoped_and_unscoped_time_add_up_to_the_ops_once():
    tr = round_trace()
    named = ("first_layer", "optimizer", "exchange", "fedavg")
    for c in tr.chips:
        inside = [e for e in trace.within(
            tr.ops[c], trace.named(tr.modules[c], scopes.ROUND))
            if not trace.CONTAINERS.match(e.name)]
        by_scope = sum(e.dur for s in named
                       for e in scopes.scoped(inside, NAMES, s))
        rest = sum(e.dur for e in inside
                   if not any(scopes.in_scope(NAMES[e.name], s)
                              for s in named))
        assert by_scope + rest == sum(e.dur for e in inside)


def ctx_of(tr, steps=4):
    return {"trace": tr, "run": {"traced_steps": steps},
            "config": {"name": "made-up"}, "traffic": {}}


def test_round_readers_normalise_per_step_and_per_round(mapped):
    ctx = ctx_of(round_trace())
    read = catalog.reader
    assert read("first_layer_device_us.train")(ctx) == \
        pytest.approx(240 * 1e-3 / 4)
    assert read("optimizer_device_us.train")(ctx) == \
        pytest.approx(20 * 1e-3 / 4)
    assert read("fedavg_device_us.train")(ctx) == \
        pytest.approx(40 * 1e-3 / 2)


def test_round_readers_read_nothing_without_scopes_or_chips(
        monkeypatch, mapped):
    tr = round_trace()
    for name in ("first_layer_device_us.train",
                 "optimizer_device_us.train", "fedavg_device_us.train"):
        read = catalog.reader(name)
        assert read(ctx_of(trace.Trace(window=(0.0, 1.0)))) is None
        assert read(ctx_of(tr, steps=0)) is None or \
            name == "fedavg_device_us.train"
        assert read({**ctx_of(tr), "trace": None}) is None
    monkeypatch.setattr(scopes, "round_stacks", lambda ctx: {})
    for name in ("first_layer_device_us.train", "fedavg_device_us.train"):
        assert catalog.reader(name)(ctx_of(tr)) is None


def host_trace(host):
    return trace.Trace(ops={0: [ev("op", 0, 1)]}, host=host,
                       window=(0.0, 1e9))


def test_span_readers_normalise_per_call_and_per_request():
    s = scopes.SPAN
    calls = host_trace([
        ev(s + "run", 0, 10_000_000), ev(s + "init", 0, 1_000_000),
        ev(s + "eval", 2_000_000, 4_000_000),
        ev(s + "predict", 2_000_000, 3_000_000),
        ev(s + "eval", 6_000_000, 7_000_000),
        ev(s + "run", 20_000_000, 30_000_000),
        ev(s + "init", 20_000_000, 23_000_000),
        ev("$session.py run", 0, 10_000_000)])
    assert catalog.reader("eval_ms.train")(ctx_of(calls)) == \
        pytest.approx(3.0 / 2)
    assert catalog.reader("init_ms.train")(ctx_of(calls)) == \
        pytest.approx(4.0 / 2)
    # an eval's fetch waits for the round dispatched before it: the
    # device time of that round inside the eval span is not eval's
    calls.modules = {0: [ev("jit_round_fn(1)", 1_000_000, 3_500_000),
                         ev("jit_predict(2)", 3_500_000, 3_600_000)]}
    assert catalog.reader("eval_ms.train")(ctx_of(calls)) == \
        pytest.approx((3.0 - 1.5) / 2)
    serve = host_trace([
        ev(s + "step", 0, 3000), ev(s + "admit", 0, 100),
        ev(s + "step", 5000, 6000),
        # a submit that offers inside it, then two offers outside
        ev(s + "submit", 10_000, 14_000), ev(s + "offer", 11_000, 12_000),
        ev(s + "submit", 20_000, 21_000), ev(s + "offer", 22_000, 23_000),
        ev(s + "offer", 24_000, 26_000)])
    assert catalog.reader("serve_step_host_us")(ctx_of(serve)) == \
        pytest.approx(2.0)
    assert catalog.reader("serve_assembly_us_per_req")(ctx_of(serve)) \
        == pytest.approx((4 + 1 + 1 + 2) / 2)


@pytest.mark.parametrize("name", ["eval_ms.train", "init_ms.train",
                                  "serve_step_host_us",
                                  "serve_assembly_us_per_req"])
def test_span_readers_read_nothing_without_spans_or_chips(name):
    read = catalog.reader(name)
    s = scopes.SPAN
    assert read(ctx_of(host_trace([]))) is None
    assert read({**ctx_of(host_trace([])), "trace": None}) is None
    # a CPU run's trace: spans on the host, no device plane
    spans = [ev(s + n, 0, 10) for n in ("run", "init", "eval", "step",
                                        "submit", "offer")]
    assert read(ctx_of(trace.Trace(host=spans,
                                   window=(0.0, 100.0)))) is None


@pytest.mark.parametrize("name", ["device_idle.train",
                                  "step_device_us.train",
                                  "serve_step_device_us",
                                  "device_idle.serve"])
def test_existing_readers_ignore_the_program_spans(name):
    tr = round_trace()
    s = scopes.SPAN
    spans = [ev(s + "run", 0, 7000), ev(s + "round", 0, 900),
             ev(s + "eval", 950, 990), ev(s + "step", 5000, 6000)]
    with_spans = trace.Trace(ops=tr.ops, modules=tr.modules,
                             host=tr.host + spans, window=tr.window)
    ctx = {"trace": tr, "run": {"traced_steps": 4}}
    assert catalog.reader(name)({**ctx, "trace": with_spans}) == \
        catalog.reader(name)(ctx)


def test_overlap_of_two_event_lists():
    a = [ev("a", 0, 10), ev("a", 5, 20), ev("a", 30, 40)]
    b = [ev("b", 15, 35), ev("b", 38, 50)]
    assert scopes.overlap_ns(a, b) == (20 - 15) + (35 - 30) + (40 - 38)
    assert scopes.overlap_ns(a, []) == 0 == scopes.overlap_ns([], b)


def test_idle_time_goes_to_the_innermost_open_span():
    s = scopes.SPAN
    tr = trace.Trace(
        ops={0: [ev("op", 0, 10), ev("op", 60, 70), ev("op", 95, 100)],
             1: [ev("op", 0, 100)]},
        host=[ev(s + "run", 0, 90), ev(s + "eval", 20, 80),
              ev(s + "score", 30, 40), ev("$session.py run", 0, 90)],
        window=(0.0, 100.0))
    idle = scopes.idle_by_span(tr)
    # chip 0 idles 10-60, 70-95; chip 1 never
    assert idle == pytest.approx({s + "run": 10e-9 + 10e-9,
                                  s + "eval": 10e-9 + 20e-9 + 10e-9,
                                  s + "score": 10e-9,
                                  "no span": 5e-9})
    assert sum(idle.values()) == pytest.approx(75e-9)


def test_stacks_map_compiled_instructions_to_their_scopes():
    def loss(w, x):
        with jax.named_scope("first_layer"):
            h = jnp.tanh(x @ w)
        with jax.named_scope("loss"):
            return (h ** 2).sum()

    @jax.jit
    def step(w, x):
        g = jax.grad(loss)(w, x)
        with jax.named_scope("optimizer"):
            return w - 0.1 * g

    text = step.lower(jnp.ones((8, 8)), jnp.ones((4, 8))).compile() \
        .as_text()
    names = scopes.stacks(text)
    assert names, text
    got = {s for s in ("first_layer", "loss", "optimizer")
           if any(scopes.in_scope(v, s) for v in names.values())}
    assert got == {"first_layer", "loss", "optimizer"}


def test_round_stacks_reads_the_cells_round_program():
    cell = small_cell("mnist5.train")
    cfg = cell["config"]
    mlp = catalog.family(cfg["model"]["family"])
    mlp.session(cfg, mlp.rows(cfg, 2**31 + 31), 2**31 + 31, rounds=1,
                eval_every=0)
    names = scopes.round_stacks({"config": cfg,
                                 "traffic": cell["traffic"]})
    for scope in ("batch", "first_layer", "tower", "exchange", "loss",
                  "optimizer", "fedavg"):
        assert any(scopes.in_scope(v, scope) for v in names.values()), \
            scope
