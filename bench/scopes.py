"""The program's own names in a traced window: the host spans it opens
and the named scopes of its device programs.

Host spans.  ``repro.obs`` puts every span on the profiler's host
timeline as ``devertifl.<name>`` (run, init, round, eval, predict,
score; submit, offer, step, admit, upload, fetch, complete), whatever
the session's ``obs`` level.  ``spans(trace, name)`` returns them.

Named scopes.  The profiler names a device operation by its HLO
instruction (``fusion.30``).  The ``jax.named_scope`` path the program
opened around it (``batch``, ``first_layer``, ``tower``, ``exchange``,
``loss``, ``optimizer``, ``fedavg``, and ``wire``, ``guard``, ``taps``
where those run) is the instruction's ``op_name`` metadata, as in
``jit(round_fn)/while/body/transpose(jvp(first_layer))/dot_general``;
a fusion carries its root's.  ``bench.trace.load`` keeps the
instruction's name only, so ``round_stacks`` maps names to paths from
the round program's compiled HLO text: the same program, compiled again
in this process for the configuration (the persistent compile cache
hands back the executable the window ran).  ``in_scope`` attributes an
op to a scope only when the scope is a whole path component, bare or
inside autodiff's ``jvp(...)``/``transpose(...)`` wrappers.

Like every trace reader, these read nothing from a trace with no
device plane (a CPU run): the spans and scopes are read against the
chip's timeline.
"""
from __future__ import annotations

import heapq
import re
from typing import Dict, List, Optional

from bench import trace

SPAN = "devertifl."
ROUND = r"round_fn"
# %fusion.30 = f32[..] fusion(..), .., metadata={.. op_name=".." ..}
_INSTRUCTION = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?'
                          r'metadata=\{[^}]*?op_name="([^"]*)"')


def spans(tr: trace.Trace, name: str) -> List[trace.Event]:
    """The host spans ``devertifl.<name>`` in the window."""
    if not tr.chips:
        return []
    return [e for e in tr.host if e.name == SPAN + name]


def overlap_ns(a: List[trace.Event], b: List[trace.Event]) -> float:
    """Time in which an event of ``a`` and an event of ``b`` both run."""
    out, bs, j = 0.0, trace.merge(b), 0
    for s, e in trace.merge(a):
        while j < len(bs) and bs[j][1] <= s:
            j += 1
        k = j
        while k < len(bs) and bs[k][0] < e:
            out += min(e, bs[k][1]) - max(s, bs[k][0])
            k += 1
    return out


def idle_by_span(tr: trace.Trace) -> Dict[str, float]:
    """Device-idle seconds in the window by the innermost program span
    the host was in ("no span" outside them), summed over the chips:
    one sweep over the spans' and the idle gaps' edges gives every
    idle piece to the shortest span open over it."""
    spans = [e for e in tr.host if e.name.startswith(SPAN)]
    lo, hi = tr.window
    out: Dict[str, float] = {}
    for c in tr.chips:
        # at one instant: spans close (0) and open (1), gaps end (2)
        # and begin (3)
        edges = [(e.end, 0, i) for i, e in enumerate(spans)]
        edges += [(e.start, 1, i) for i, e in enumerate(spans)]
        for s, e in trace.gaps(tr.ops[c], lo, hi):
            edges += [(e, 2, -1), (s, 3, -1)]
        edges.sort()
        open_, closed, idle, t = [], set(), False, lo
        for when, kind, i in edges:
            if idle and when > t:
                while open_ and open_[0][1] in closed:
                    heapq.heappop(open_)
                name = spans[open_[0][1]].name if open_ else "no span"
                out[name] = out.get(name, 0.0) + (when - t) * 1e-9
            t = when
            if kind == 0:
                closed.add(i)
            elif kind == 1:
                heapq.heappush(open_, (spans[i].dur, i))
            else:
                idle = kind == 3
    return out


def stacks(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> its ``op_name`` metadata, from HLO text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def in_scope(stack: str, scope: str) -> bool:
    """Whether ``scope`` is a whole component of the name stack, bare
    or wrapped (``jvp(scope)``, ``transpose(jvp(scope))``)."""
    for part in stack.split("/"):
        while part.endswith(")") and "(" in part:
            part = part[part.index("(") + 1:-1]
        if part == scope:
            return True
    return False


def scoped(events: List[trace.Event], names: Dict[str, str],
           scope: str) -> List[trace.Event]:
    """The events of operations under ``scope``, container ops
    (``trace.CONTAINERS``, such as a scan's ``while``) left out as
    ``trace.breakdown`` leaves them out."""
    return [e for e in events if not trace.CONTAINERS.match(e.name)
            and in_scope(names.get(e.name, ""), scope)]


def device_ns(tr: trace.Trace, names: Dict[str, str], scope: str,
              program: str = ROUND) -> Optional[float]:
    """Device time of ``scope``'s operations inside the modules named
    by ``program``, averaged over the chips that ran any; None where
    none ran."""
    per_chip = []
    for c in tr.chips:
        inside = trace.within(tr.ops[c], trace.named(tr.modules.get(c, []),
                                                     program))
        ns = sum(e.dur for e in scoped(inside, names, scope))
        if ns > 0:
            per_chip.append(ns)
    return sum(per_chip) / len(per_chip) if per_chip else None


def rounds(tr: trace.Trace, program: str = ROUND) -> float:
    """Round programs run in the window, averaged over the chips."""
    if not tr.chips:
        return 0.0
    return sum(len(trace.named(tr.modules.get(c, []), program))
               for c in tr.chips) / len(tr.chips)


def round_stacks(ctx) -> Dict[str, str]:
    """Instruction name -> name stack of the configuration's round
    program, compiled as the training cell compiles it (a Session of
    the dataset the cell registered, through ``bench.program``; neither
    the shapes nor the partitions of the configurations depend on the
    seed).  Read once a run: kept in ``ctx``, which the run hands to
    every reader."""
    cfg, trf = ctx["config"], ctx["traffic"]
    if "round_stacks" not in ctx:
        import jax
        import jax.numpy as jnp

        from bench import program
        sess = program.build(cfg, 0, rounds=trf["rounds_per_call"],
                             eval_every=trf["eval_every"])
        fed = sess.federation
        key = jax.random.PRNGKey(0)
        params = jax.eval_shape(fed.init_params, key)
        opt_state = jax.eval_shape(jax.vmap(fed.opt.init), params)
        lowered = fed._round.lower(
            params, opt_state, jax.ShapeDtypeStruct((), jnp.int32),
            fed.init_sched_state(), key, fed._xtr, fed._ytr, fed._lay)
        ctx["round_stacks"] = stacks(lowered.compile().as_text())
    return ctx["round_stacks"]


def round_scope(ctx, scope: str) -> Optional[float]:
    """Device ns of ``scope`` inside the round program in the traced
    window (``device_ns``); None without a device trace or scope."""
    tr = ctx.get("trace")
    if tr is None or not tr.chips or not any(tr.ops.values()):
        return None
    return device_ns(tr, round_stacks(ctx), scope)
