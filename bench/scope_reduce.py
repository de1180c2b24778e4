"""Put a profiler trace's device time down to the program's named scopes.

The program names its layers with ``jax.named_scope`` (``sched.*`` for
the scheduler's switch branches, ``lm.*`` for the model's layers; see
``repro/obs/scopes.py``) and times its host phases as ``sched.*`` and
``launch.*`` spans.  The trace does not carry the scopes: a device op's
event is named by its HLO instruction, and the name stack lives in the
instruction's ``metadata={op_name="..."}`` in the compiled module's
text (``ContinuousBatchingScheduler.compiled_loop_text``).  Instruction
names are unique only within a module, so an op is first put in the
module whose ``XLA Modules`` event holds it.

What it reads (``load``), beside what ``trace_reduce.load`` reads:

* device ops with their full instruction name (``fusion.12``) and the
  name of the module they ran in (``jit_serve_loop``), on the host
  clock (``trace_reduce``'s shift);
* host events named ``sched.*`` or ``launch.*``: the program's spans.

What it computes, within a window on the host clock:

* ``self_seconds``: each op's own time -- its interval less the ops
  nested inside it, so the loop's ``while`` and ``conditional``
  containers keep only their control time -- summed per (module,
  instruction); the sum over ops is the busy time of the plane;
* ``by_stack``: those seconds by name stack, for one module and its
  HLO text, with what does not resolve kept apart;
* ``under``: the seconds whose stack holds a given scope;
* ``host_by_span``: host seconds of the program's spans, and the idle
  device gaps put down to the innermost program span around them.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

import trace_reduce

Interval = Tuple[float, float]
PROGRAM_SPANS = ("sched.", "launch.")

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([A-Za-z0-9_.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_MODULE = re.compile(r"^HloModule\s+([A-Za-z0-9_.\-]+)")


def instr_name(text: str) -> str:
    """'%fusion.12 = f32[8] fusion(...)' -> 'fusion.12'."""
    m = _INSTR.match(text)
    return m.group(1) if m else text.split(" ", 1)[0].lstrip("%")


def module_name(event_name: str) -> str:
    """'jit_serve_loop(1234)' -> 'jit_serve_loop'."""
    return event_name.split("(", 1)[0]


@dataclasses.dataclass
class ScopeTrace:
    """``ops``: per device plane, (instruction, module, start, end);
    ``host``: the program's spans (name, start, end); ``base``: the
    same trace as ``trace_reduce`` reads it."""
    ops: List[List[Tuple[str, str, float, float]]]
    host: List[Tuple[str, float, float]]
    base: trace_reduce.Trace


def load(path: str) -> ScopeTrace:
    from jax.profiler import ProfileData

    base = trace_reduce.load(path)
    shift = base.shift_s
    pd = ProfileData.from_file(path)
    ops, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            mods, evs = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods += [(*_span(e, shift), module_name(e.name))
                             for e in line.events]
                elif line.name == "XLA Ops":
                    evs += [(instr_name(e.name), *_span(e, shift))
                            for e in line.events]
            ops.append(_in_modules(evs, mods))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PROGRAM_SPANS):
                        t = e.start_ns * 1e-9
                        host.append((e.name, t, t + e.duration_ns * 1e-9))
    return ScopeTrace(ops=ops, host=sorted(host, key=lambda s: s[1]),
                      base=base)


def _span(e, shift: float) -> Tuple[float, float]:
    """An event's (start, end) in seconds as ``trace_reduce`` puts it."""
    t = e.start_ns * 1e-9
    return t + shift, t + e.duration_ns * 1e-9 + shift


def _in_modules(evs, mods) -> List[Tuple[str, str, float, float]]:
    """Each op with the module whose event holds its start ('' if none)."""
    mods = sorted(mods)
    starts = [m[0] for m in mods]
    out = []
    for name, a, b in evs:
        i = bisect.bisect_right(starts, a) - 1
        mod = mods[i][2] if i >= 0 and a < mods[i][1] else ""
        out.append((name, mod, a, b))
    return out


def name_stacks(hlo_text: str) -> Tuple[str, Dict[str, str]]:
    """(module name, {instruction: op_name}) of an HLO text.

    An instruction the compiler made without metadata (a copy of the
    loop's carry, say) takes its computation's: the op_name path the
    computation's named instructions share, so a copy inside the step
    branch reads '.../branch_3_fun/sched.step'."""
    module, out = "", {}
    comp: List[Tuple[str, str]] = []           # the open computation
    for line in hlo_text.splitlines():
        if not module:
            m = _MODULE.match(line)
            if m:
                module = m.group(1)
            continue
        if line.startswith("}"):
            _close_computation(comp, out)
            comp = []
            continue
        m = _INSTR.match(line) if line.startswith(" ") else None
        if not m:
            continue
        name = _OP_NAME.search(m.group(2))
        comp.append((m.group(1), name.group(1) if name else ""))
    _close_computation(comp, out)
    return module, out


def _close_computation(comp, out) -> None:
    named = [n.split("/") for _, n in comp if n]
    k = 0
    while named and all(len(n) > k and n[k] == named[0][k] for n in named):
        k += 1
    shared = "/".join(named[0][:k]) if named else ""
    for instr, name in comp:
        out[instr] = name or shared


def self_seconds(trace: ScopeTrace, window: Interval,
                 plane: int = 0) -> Dict[Tuple[str, str], float]:
    """{(module, instruction): seconds} inside the window.  Each instant
    of device time goes to the latest-started op running then: an op
    keeps its interval less the ops nested in it, and of two ops that
    overlap without nesting the later one takes the overlap.  The
    seconds sum to the union of the ops' intervals."""
    t0, t1 = window
    evs = sorted(((max(a, t0), min(b, t1), (mod, name))
                  for name, mod, a, b in trace.ops[plane]
                  if b > t0 and a < t1), key=lambda e: (e[0], -e[1]))
    own: Dict[Tuple[str, str], float] = defaultdict(float)
    stack: List[Tuple[float, Tuple[str, str]]] = []   # open ops (end, key)
    t = t0                                            # given out up to t
    for a, b, key in evs + [(t1, t1, None)]:
        while stack and t < a:
            end, top = stack[-1]
            if end > t:
                stop = min(end, a)
                own[top] += stop - t
                t = stop
            if end <= t:
                stack.pop()
        t = max(t, a)
        if key is not None:
            stack.append((b, key))
    return dict(own)


def components(op_name: str) -> Tuple[str, ...]:
    """The program's scopes in an op_name, outermost first:
    'jit(f)/while/body/cond/branch_3_fun/sched.step/lm.step/lm.cim/dot'
    -> ('sched.step', 'lm.step', 'lm.cim')."""
    return tuple(c for c in op_name.split("/")
                 if c.startswith(("sched.", "lm.")))


def by_stack(own: Dict[Tuple[str, str], float], hlo_text: str
             ) -> Tuple[Dict[Tuple[str, ...], float], float, float]:
    """(seconds by scope stack, unresolved seconds, other modules'
    seconds) of the ops of the HLO text's module.  An op of the module
    that the text names with an op_name resolves, to the tuple of its
    ``sched.*``/``lm.*`` scopes (empty for the loop's own control)."""
    module, stacks = name_stacks(hlo_text)
    out: Dict[Tuple[str, ...], float] = defaultdict(float)
    unresolved = other = 0.0
    for (mod, name), secs in own.items():
        if mod != module:
            other += secs
            continue
        path = stacks.get(name)
        if path:
            out[components(path)] += secs
        else:
            unresolved += secs
    return dict(out), unresolved, other


def under(stacks: Dict[Tuple[str, ...], float], scope: str) -> float:
    """Seconds whose scope stack holds ``scope``."""
    return sum(s for k, s in stacks.items() if scope in k)


def outside(stacks: Dict[Tuple[str, ...], float], prefix: str) -> float:
    """Seconds whose scope stack holds no scope starting with ``prefix``."""
    return sum(s for k, s in stacks.items()
               if not any(c.startswith(prefix) for c in k))


def split(stacks: Dict[Tuple[str, ...], float]
          ) -> List[Tuple[str, str, float]]:
    """(branch, innermost lm.* scope, seconds), largest first: the branch
    is the first ``sched.*`` scope ('' for the loop's control), the
    layer '' where no ``lm.*`` scope is open."""
    tot: Dict[Tuple[str, str], float] = defaultdict(float)
    for k, s in stacks.items():
        branch = next((c for c in k if c.startswith("sched.")), "")
        layer = next((c for c in reversed(k) if c.startswith("lm.")), "")
        tot[branch, layer] += s
    return sorted(((b, l, s) for (b, l), s in tot.items()),
                  key=lambda x: -x[2])


def host_by_span(trace: ScopeTrace, window: Interval
                 ) -> Dict[str, float]:
    """Host seconds of each program span name inside the window."""
    t0, t1 = window
    tot: Dict[str, float] = defaultdict(float)
    for name, a, b in trace.host:
        if b > t0 and a < t1:
            tot[name] += min(b, t1) - max(a, t0)
    return dict(tot)


def idle_by_span(trace: ScopeTrace, window: Interval, plane: int = 0
                 ) -> List[Tuple[str, float]]:
    """Idle device seconds inside the window by the innermost program
    span around each gap's midpoint, largest first."""
    base = dataclasses.replace(trace.base, spans=trace.host)
    gaps = trace_reduce.idle_gaps(base, window, plane)
    return [(label.replace("outside bench spans", "outside program spans"),
             secs) for label, secs in trace_reduce.gaps_by_label(gaps)]


def run_stacks(run) -> Optional[Dict[Tuple[str, ...], float]]:
    """Serve-loop device seconds by scope stack of a benchmark ``Run``
    that carries ``scope_trace`` and ``loop_text``; None without them."""
    trace = getattr(run, "scope_trace", None)
    text = getattr(run, "loop_text", None)
    if trace is None or not text or run.window is None or not trace.ops:
        return None
    return by_stack(self_seconds(trace, run.window), text)[0]
