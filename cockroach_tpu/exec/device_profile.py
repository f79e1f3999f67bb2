"""Device time by plan operator, read from inside the program.

Every operator's lowering runs under `crdb.op<N>.<Kind>` (exec/fused.py
`_Tracer._scope`; N is the operator's pre-order position under
walk_operators(root)), what the distributed tracer adds to a join or an
aggregate under `crdb.op<N>.<Kind>.exchange` / `.merge`, the packed result
under `crdb.result`. A scope is debug information: it rides each
instruction's `op_name` into the compiled executable and costs a served
statement nothing. This module turns a compiled program and a profile of
its own serial executions into device milliseconds by those scopes: what
EXPLAIN ANALYZE (DEVICE) prints (sql/explain.py) and the benchmark's
`op_*_ms` metrics read (benchmark/layer_metrics/_device_profile.py).

  owners(compiled) -> {instruction name: (scope or None, how)}
    from compiled.as_text(), computed when a profile is asked for and
    never before. In this order:
    named     the instruction's own `op_name` (the innermost `crdb.`
              component wins). A fusion that XLA left without one is what
              its root is, the name XLA would have given it.
    inferred  for an instruction without a scope (an inner jit's, or one
              XLA made itself: an expansion, a layout copy): ONE operator,
              and no other, has an instruction among its nearest scoped
              producers AND one among its nearest scoped consumers: it
              lies between two instructions of that operator, and an
              operator's lowering is closed under dataflow (children feed
              parents, never back). Dataflow is followed through unscoped
              instructions over the whole module, instruction by
              instruction: a callee's parameters continue at its caller's
              operands and the caller's consumers at the callee's root, a
              fusion's body included (what XLA made of the tail of a scan
              is followed by the scan's own convert INSIDE the fusion that
              reads it). An instruction of a while or conditional body
              that this leaves open is its caller's.
    None      anything else, and `how` then says between what it stands
              ("op7.ScanOp -> op6.MapOp": a boundary; "- -> op1.MapOp":
              one side only): never a guess by shape, by position in the
              schedule or by one side alone.

  profile(dispatch, compiled, repeats, stages) -> DeviceProfile
    jax.profiler around `repeats` SERIAL executions (`dispatch()` returns
    after block_until_ready, a pause after each), no Python tracer, the
    trace directory removed. Device events are the "XLA Ops" line of each
    `/device:TPU:<n>` plane (on the CPU backend the host events with an
    `hlo_op` stat, the chip their `device_ordinal`), kept to this program's
    module. Every instant a chip is busy goes to the event that started
    last among those running (an event's SELF time: a `while` and its body
    are not both counted), so the scopes, `result` and `unattributed` sum
    to `busy_ms` exactly. The execution whose busy time is the median
    stands for all; chips are averaged, the largest chip kept beside.
    `gaps_ms` is the device idle INSIDE the program (first start to last
    end, less busy). `launch_ms` (start of the program's own `dispatch`
    annotation to the first device event) and `drain_ms` (last device
    event to the end of its `wait` annotation) are medians; serial
    executions make both non-negative, so a negative launch IS the offset
    between the trace's host and device lanes: `lane_offset_ms`, and both
    are corrected by it.

An executable without any `crdb.` scope (one that a tree without them
wrote into a shared compile cache: the cache's key does not see debug
information) yields a profile with `scoped` False and everything
unattributed.
"""

from __future__ import annotations

import glob
import os
import re
import shutil
import statistics
import tempfile
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from cockroach_tpu.exec.fused import (
    EXCHANGE, MERGE, RESULT_SCOPE, SCOPE_PREFIX, op_scope_name,
)
from cockroach_tpu.exec.operators import (
    HashAggOp, JoinOp, LimitOp, MapOp, Operator, ScanOp, ShrinkOp, SortOp,
    TopKOp, walk_operators,
)
from cockroach_tpu.util import tracing as _tracing

NAMED, INFERRED = "named", "inferred"
PAUSE_S = 0.02      # between executions: far above the lanes' offset
_TOP_UNATTRIBUTED = 8
_TOP_OPS = 16
_NOT_IN_TEXT = (None, "not in the module's text")
_THUNK_STATS = ("hlo_op", "hlo_module", "device_ordinal")

_SCOPE = re.compile(
    re.escape(SCOPE_PREFIX)
    + rf"({RESULT_SCOPE}|op\d+\.[A-Za-z_]\w*(?:\.(?:{EXCHANGE}|{MERGE}))?)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s+->\s+.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
_CALLEES = re.compile(
    r"\b(calls|to_apply|body|condition|true_computation|false_computation"
    r"|branch_computations|called_computations)=(\{[^}]*\}|%?[\w.\-]+)")
_NAME = re.compile(r"%?([\w.\-]+)")
# instructions whose callees run as instructions of their own (events of
# the trace); a fusion's callee is part of it
_CONTROL = frozenset(("while", "conditional", "call"))


def scope_of(op_name: str) -> Optional[str]:
    """The innermost `crdb.` component of an instruction's op_name, less
    the prefix: `op5.JoinOp`, `op5.JoinOp.exchange`, `result`; or None."""
    for part in reversed(op_name.split("/")):
        m = _SCOPE.match(part)
        if m:
            return m.group(1)
    return None


# ------------------------------------------------------- the HLO text ----

@dataclass
class _Instr:
    name: str
    opcode: str
    operands: List[str]
    callees: List[str]      # bodies dataflow passes through; a reducer
    #                         or comparator (to_apply) is not one
    scope: Optional[str]
    root: bool


def _balanced(text: str, start: int) -> int:
    """Index just past the parenthesis that closes text[start] == '('."""
    depth = 0
    for i in range(start, len(text)):
        c = text[i]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                return i + 1
    return len(text)


def _parse_instruction(name: str, rest: str, root: bool) -> _Instr:
    """`rest` is what follows " = ": result type, opcode(operands),
    attributes. An operand's name is its last word (a printer may put
    the operand's type before it); a parameter's is its number."""
    i = _balanced(rest, 0) if rest.startswith("(") else rest.find(" ")
    call = rest[i:].lstrip()
    paren = call.find("(")
    opcode = call[:paren].strip()
    end = _balanced(call, paren)
    operands, depth, piece = [], 0, []
    for c in call[paren + 1:end - 1] + ",":
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
        if c == "," and depth == 0:
            words = re.sub(r"/\*.*?\*/", "", "".join(piece)).split()
            if words:
                operands.append(words[-1].lstrip("%"))
            piece = []
        else:
            piece.append(c)
    attrs = call[end:]
    callees = []
    for key, val in _CALLEES.findall(attrs):
        if key != "to_apply" or opcode == "call":
            callees += _NAME.findall(val)
    m = _OP_NAME.search(attrs)
    return _Instr(name, opcode, operands, callees,
                  scope_of(m.group(1)) if m else None, root)


def parse_hlo(text: str) -> Tuple[str, Dict[str, List[_Instr]]]:
    """-> (module name, {computation: its instructions in text order}).
    A scheduled module's text order is a dataflow order, and a callee
    stands before its callers."""
    module = ""
    comps: Dict[str, List[_Instr]] = {}
    cur: Optional[List[_Instr]] = None
    for line in text.splitlines():
        if line.startswith("HloModule "):
            module = line.split()[1].rstrip(",")
            continue
        if cur is None:
            m = _COMPUTATION.match(line)
            if m:
                cur = comps.setdefault(m.group(2), [])
            continue
        if line.startswith("}"):
            cur = None
            continue
        m = _INSTRUCTION.match(line)
        if m:
            cur.append(_parse_instruction(m.group(2), m.group(3),
                                          bool(m.group(1))))
    return module, comps


_NONE: frozenset = frozenset()


def _one(scopes) -> Optional[str]:
    return next(iter(scopes)) if len(scopes) == 1 else None


class _Dataflow:
    """Nearest scoped producers (`up`) and consumers (`down`) of every
    instruction, over ONE graph of the module: a callee's parameters
    continue at its caller's operands, the caller's consumers at the
    callee's root."""

    def __init__(self, comps: Dict[str, List[_Instr]]):
        self.comps = comps
        self.instr = {i.name: i for c in comps.values() for i in c}
        self.params = {c: {int(i.operands[0]): i.name for i in instrs
                           if i.opcode == "parameter" and i.operands}
                       for c, instrs in comps.items()}
        self.roots = {c: next((i for i in instrs if i.root), instrs[-1])
                      for c, instrs in comps.items() if instrs}
        self.up: Dict[str, frozenset] = {}
        self.down: Dict[str, frozenset] = {}
        self.callers: Dict[str, List[_Instr]] = {}
        entry = list(comps)[-1]
        self._forward(entry, None, None)
        self._backward(entry, _NONE)

    def bodies(self, ins: _Instr) -> List[str]:
        """The callees whose root is what `ins` puts out."""
        cs = [c for c in ins.callees if c in self.comps]
        return cs[:1] if ins.opcode == "while" else cs  # not the condition

    def _from(self, name: str) -> frozenset:
        ins = self.instr.get(name)
        if ins is None:
            return _NONE
        return frozenset((ins.scope,)) if ins.scope else self.up.get(
            name, _NONE)

    def _forward(self, comp: str, caller: Optional[_Instr], feeds) -> None:
        for ins in self.comps[comp]:
            if ins.opcode == "parameter":
                got = _NONE
                if feeds:
                    n = int(ins.operands[0]) if ins.operands else -1
                    got = (feeds[n] if caller.opcode != "conditional"
                           and 0 <= n < len(feeds)
                           else frozenset().union(*feeds))
            else:
                feeds_in = [self._from(o) for o in ins.operands]
                got = frozenset().union(*feeds_in)
                if ins.callees:
                    for c in ins.callees:
                        if c in self.comps:
                            self.callers.setdefault(c, []).append(ins)
                            self._forward(c, ins, feeds_in)
                    if ins.scope is None:
                        got = frozenset().union(*(
                            self._from(self.roots[c].name)
                            for c in self.bodies(ins)))
            self.up[ins.name] = got

    def _into(self, user: _Instr, index: int) -> frozenset:
        """What operand `index` of `user` reaches."""
        got = set()
        for c in user.callees:      # the callee's instructions first
            ps = self.params.get(c, {})
            names = ([ps[index]] if user.opcode != "conditional"
                     and index in ps else ps.values())
            for p in names:
                got |= self.down.get(p, _NONE)
        if got:
            return frozenset(got)
        if user.scope:
            return frozenset((user.scope,))
        return _NONE if user.callees else self.down.get(user.name, _NONE)

    def _backward(self, comp: str, root_down: frozenset) -> None:
        instrs = self.comps[comp]
        users: Dict[str, List[Tuple[_Instr, int]]] = {}
        for ins in instrs:
            if ins.opcode != "parameter":
                for n, o in enumerate(ins.operands):
                    users.setdefault(o, []).append((ins, n))
        for ins in reversed(instrs):
            got = set(root_down) if ins.root else set()
            for user, n in users.get(ins.name, ()):
                got |= self._into(user, n)
            self.down[ins.name] = frozenset(got)
            for c in ins.callees:
                if c in self.comps:
                    self._backward(c, self.down[ins.name])


def owners_of_text(text: str) -> Tuple[str, Dict[str, Tuple[Optional[str],
                                                            Optional[str]]]]:
    """-> (module name, {instruction: (scope, how)}) for every
    instruction the dataflow reaches (module docstring). For one that no
    rule owns, (None, "<nearest scoped producers> -> <consumers>")."""
    module, comps = parse_hlo(text)
    if not comps:
        return module, {}
    flow = _Dataflow(comps)
    out: Dict[str, Tuple[Optional[str], Optional[str]]] = {}

    def root_owner(comp: str):
        """A caller without a scope is what its callee's root is: the
        name XLA would have given it."""
        root = flow.roots[comp]
        if root.scope is None and root.opcode == "tuple":
            parts = {getattr(flow.instr.get(o), "scope", None)
                     for o in root.operands}
            if None not in parts and len(parts) == 1:
                return parts.pop(), NAMED
        return out.get(root.name, (None, "-"))

    for comp, instrs in comps.items():     # callees first
        for ins in instrs:
            if ins.name not in flow.up:
                continue                    # a reducer: nothing calls it here
            if ins.scope is not None:
                out[ins.name] = (ins.scope, NAMED)
            elif ins.callees:
                got = {root_owner(c) for c in flow.bodies(ins)}
                out[ins.name] = (got.pop() if len(got) == 1
                                 else (None, "roots disagree"))
            else:
                u, d = flow.up[ins.name], flow.down[ins.name]
                one = _one(u & d)
                out[ins.name] = (one, INFERRED) if one else (
                    None, f"{', '.join(sorted(u)) or '-'} -> "
                          f"{', '.join(sorted(d)) or '-'}")
    # an instruction of a while or conditional body that dataflow leaves
    # open runs as part of its caller: callers first
    for comp in reversed(list(comps)):
        by = {out[c.name][0] for c in flow.callers.get(comp, ())
              if c.opcode in _CONTROL}
        if len(by) == 1 and None not in by:
            for ins in comps[comp]:
                if out.get(ins.name, (1,))[0] is None:
                    out[ins.name] = (next(iter(by)), INFERRED)
    return module, out


def owners(compiled) -> Dict[str, Tuple[Optional[str], Optional[str]]]:
    return owners_of_text(compiled.as_text())[1]


# ---------------------------------------------------------- the trace ----

def self_times(spans: Sequence[Tuple[float, float]]) -> List[float]:
    """Of [(start, end)], each span's share of the union of them all:
    every instant goes to the span that started last among those running
    (ties: the shorter), so nested spans count once and the shares sum to
    the union."""
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i][0], -spans[i][1]))
    out = [0.0] * len(spans)
    stack: List[int] = []
    t = 0.0

    def run_until(limit: float) -> None:
        nonlocal t
        while stack and t < limit:
            top = stack[-1]
            end = spans[top][1]
            if end <= t:
                stack.pop()
                continue
            upto = min(end, limit)
            out[top] += upto - t
            t = upto

    for i in order:
        run_until(spans[i][0])
        t = spans[i][0]
        stack.append(i)
    run_until(float("inf"))
    return out


def load_events(path: str, module: str) -> dict:
    """-> {"device": [(instruction, start_ns, end_ns, chip)],
    "host": {annotation name: [(start_ns, end_ns)]}} of one xplane file:
    the device events of `module` (module docstring) and the program's
    own `crdb.` annotations."""
    from jax.profiler import ProfileData

    device, host = [], {}
    for plane in ProfileData.from_file(path).planes:
        pname = plane.name
        if pname.startswith("/device:TPU:"):
            chip = int(pname.rsplit(":", 1)[1].split()[0])
            lines = list(plane.lines)
            runs = [(e.start_ns, e.start_ns + e.duration_ns)
                    for ln in lines if ln.name == "XLA Modules"
                    for e in ln.events if e.name.startswith(module)]
            for ln in lines:
                if ln.name != "XLA Ops":
                    continue
                for e in ln.events:
                    s, t = e.start_ns, e.start_ns + e.duration_ns
                    if runs and not any(a <= s and t <= b for a, b in runs):
                        continue
                    name = e.name.partition(" = ")[0].lstrip("%")
                    device.append((name, float(s), float(t), chip))
        elif pname.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    name = e.name
                    if name.startswith(_tracing.ANNOTATION_PREFIX):
                        host.setdefault(
                            name[len(_tracing.ANNOTATION_PREFIX):], []).append(
                            (float(e.start_ns),
                             float(e.start_ns + e.duration_ns)))
                    elif e.duration_ns > 0 and not name.startswith("end:"):
                        # the CPU backend's thunks: host events that say
                        # which instruction of which module they ran
                        st = {k: v for k, v in e.stats if k in _THUNK_STATS}
                        if st.get("hlo_module") == module and "hlo_op" in st:
                            device.append((
                                str(st["hlo_op"]), float(e.start_ns),
                                float(e.start_ns + e.duration_ns),
                                int(st.get("device_ordinal", 0))))
    return {"device": device, "host": host}


@dataclass
class DeviceProfile:
    """Milliseconds of ONE execution (module docstring). `scopes` maps a
    scope (`op5.JoinOp`, `op5.JoinOp.exchange`, `result`) to its
    [named, inferred] self time; `largest` to its time on the chip where
    it was largest."""

    executions: int = 0
    chips: int = 0
    scoped: bool = False
    busy_ms: float = 0.0
    busy_max_ms: float = 0.0
    gaps_ms: float = 0.0
    launch_ms: float = 0.0
    drain_ms: float = 0.0
    lane_offset_ms: float = 0.0
    scopes: Dict[str, List[float]] = field(default_factory=dict)
    largest: Dict[str, float] = field(default_factory=dict)
    unattributed_ms: float = 0.0
    # the instructions no rule owns, by time: [[name, ms, "nearest
    # scoped producers -> consumers"]]
    unattributed_ops: List[list] = field(default_factory=list)
    # the instructions that took most, each with its owner:
    # [[name, ms, scope or None, how]]
    top_ops: List[list] = field(default_factory=list)
    profile_s: float = 0.0      # what taking the profile cost, in all

    def scope_ms(self, scope: str) -> float:
        return sum(self.scopes.get(scope, (0.0, 0.0)))


def reduce_events(events: dict, owner: Dict[str, tuple],
                  stages: Tuple[str, str]) -> DeviceProfile:
    """The arithmetic of profile(), on load_events()'s output: testable
    without a profile file."""
    dispatches = sorted(events["host"].get(stages[0], ()))
    waits = sorted(events["host"].get(stages[1], ()))
    n = min(len(dispatches), len(waits))
    prof = DeviceProfile(executions=n, scoped=any(
        scope is not None for scope, _how in owner.values()))
    dev = sorted(events["device"], key=lambda e: e[1])
    if not n or not dev:
        return prof
    # an execution owns the device events up to the middle of the pause
    # that follows it
    cuts = [0.5 * (waits[i][1] + dispatches[i + 1][0]) for i in range(n - 1)]
    cuts.append(float("inf"))
    chips = sorted({e[3] for e in dev})
    prof.chips = len(chips)
    per_exec, k = [], 0
    for i in range(n):
        mine = []
        while k < len(dev) and dev[k][1] < cuts[i]:
            mine.append(dev[k])
            k += 1
        per_exec.append(mine)
    runs = []   # an execution: {chip: (busy, extent, first, last, by name)}
    for mine in per_exec:
        by_chip = {}
        for c in chips:
            evs = [e for e in mine if e[3] == c]
            if not evs:
                continue
            shares = self_times([(e[1], e[2]) for e in evs])
            by_name: Dict[str, float] = {}
            for e, s in zip(evs, shares):
                by_name[e[0]] = by_name.get(e[0], 0.0) + s
            first, last = min(e[1] for e in evs), max(e[2] for e in evs)
            by_chip[c] = (sum(shares), last - first, first, last, by_name)
        runs.append(by_chip)
    live = [i for i, r in enumerate(runs) if r]
    if not live:
        return prof

    def mean_busy(i):
        return statistics.fmean(v[0] for v in runs[i].values())

    # the execution whose busy time is the median (the lower of two)
    mid = sorted(live, key=mean_busy)[(len(live) - 1) // 2]
    run = runs[mid]
    nc = len(run)
    prof.busy_ms = mean_busy(mid) / 1e6
    prof.busy_max_ms = max(v[0] for v in run.values()) / 1e6
    prof.gaps_ms = statistics.fmean(v[1] - v[0] for v in run.values()) / 1e6
    unowned: Dict[str, float] = {}
    every: Dict[str, float] = {}
    for _busy, _extent, _first, _last, by_name in run.values():
        on_chip: Dict[str, float] = {}
        for name, ns in by_name.items():
            scope, how = owner.get(name, _NOT_IN_TEXT)
            ms = ns / 1e6
            every[name] = every.get(name, 0.0) + ms / nc
            if scope is None:
                prof.unattributed_ms += ms / nc
                unowned[name] = unowned.get(name, 0.0) + ms / nc
                continue
            pair = prof.scopes.setdefault(scope, [0.0, 0.0])
            pair[0 if how == NAMED else 1] += ms / nc
            on_chip[scope] = on_chip.get(scope, 0.0) + ms
        for scope, ms in on_chip.items():
            prof.largest[scope] = max(prof.largest.get(scope, 0.0), ms)

    def top(ms_by_name: Dict[str, float], n: int) -> list:
        return sorted(ms_by_name.items(), key=lambda kv: -kv[1])[:n]

    prof.unattributed_ops = [
        [name, ms, owner.get(name, _NOT_IN_TEXT)[1]]
        for name, ms in top(unowned, _TOP_UNATTRIBUTED)]
    prof.top_ops = [[name, ms, *owner.get(name, _NOT_IN_TEXT)]
                    for name, ms in top(every, _TOP_OPS)]
    launches = [(min(v[2] for v in runs[i].values()) - dispatches[i][0]) / 1e6
                for i in live]
    drains = [(waits[i][1] - max(v[3] for v in runs[i].values())) / 1e6
              for i in live]
    launch, drain = statistics.median(launches), statistics.median(drains)
    prof.lane_offset_ms = max(0.0, -launch)
    prof.launch_ms = launch + prof.lane_offset_ms
    prof.drain_ms = drain - prof.lane_offset_ms
    return prof


def profile(dispatch: Callable[[], None], compiled, repeats: int,
            stages: Tuple[str, str]) -> DeviceProfile:
    """Profile `repeats` serial executions of `compiled` (module
    docstring). `dispatch()` runs the program once under the program's
    own annotations `stages` = (dispatch, wait) (util/tracing.annotation)
    and returns once the device is done."""
    import jax

    t0 = time.perf_counter()
    module, owner = owners_of_text(compiled.as_text())
    trace_dir = tempfile.mkdtemp(prefix="crdb-device-profile-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            for _ in range(repeats):
                dispatch()
                time.sleep(PAUSE_S)
        finally:
            jax.profiler.stop_trace()
        paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                                 recursive=True))
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
        events = load_events(paths[-1], module)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    prof = reduce_events(events, owner, stages)
    prof.profile_s = time.perf_counter() - t0
    return prof


def run_annotated(call: Callable[[], object],
                  stages: Tuple[str, str]) -> Callable[[], None]:
    """dispatch() for profile(): `call()` enqueues the program under the
    first stage's annotation, block_until_ready runs under the second's:
    the two halves a served statement's `fused.exec` / `dist.exec` has."""
    import jax

    def dispatch():
        with _tracing.annotation(stages[0]):
            out = call()
        with _tracing.annotation(stages[1]):
            jax.block_until_ready(out)

    return dispatch


# ------------------------------------------- the operator's table ----

def _label(op: Operator) -> str:
    """A few words that tell one operator of a kind from another."""
    if isinstance(op, ScanOp):
        return str(getattr(op, "table", None) or "")
    if isinstance(op, JoinOp):
        # a semi / anti join with a residual is named as the plan has
        # it; it runs as the inner / left join that feeds the residual's
        # filter (sql/plan.build)
        of = getattr(op, "residual_of", None)
        return (f"{of}+residual " if of else f"{op.how} ") + ", ".join(
            f"{a} = {b}" for a, b in zip(op.probe_on, op.build_on))
    if isinstance(op, HashAggOp):
        return ("group by " + ", ".join(op.group_by) if op.group_by
                else "scalar")
    if isinstance(op, ShrinkOp):
        return f"to {op.capacity} lanes"
    if isinstance(op, TopKOp):
        return f"top {op.k}"
    if isinstance(op, LimitOp):
        return f"limit {op.limit}"
    if isinstance(op, SortOp):
        return "by " + ", ".join(str(getattr(k, "col", k)) for k in op.keys)
    if isinstance(op, MapOp):
        kinds = [kind for kind, _payload in op.steps]
        return " + ".join(sorted(set(kinds), key=kinds.index))
    return ""


def operator_rows(root: Operator, prof: DeviceProfile) -> List[dict]:
    """One row an operator of the built tree in pre-order, then one for
    each `.exchange` / `.merge` part that has time: {"n", "kind", "part",
    "label", "device_ms", "named_ms", "inferred_ms", "largest_chip_ms"}."""
    rows = []
    for n, op in enumerate(walk_operators(root)):
        for part in ("", EXCHANGE, MERGE):
            scope = op_scope_name(n, op, part)
            if part and scope not in prof.scopes:
                continue
            named_ms, inferred_ms = prof.scopes.get(scope, (0.0, 0.0))
            rows.append({"n": n, "kind": type(op).__name__, "part": part,
                         "label": _label(op),
                         "device_ms": named_ms + inferred_ms,
                         "named_ms": named_ms, "inferred_ms": inferred_ms,
                         "largest_chip_ms": prof.largest.get(scope, 0.0)})
    return rows


def as_dict(root: Operator, prof: DeviceProfile) -> dict:
    """The profile of one statement as the benchmark's `device_profile`
    line and the statement bundle carry it."""
    return {"executions": prof.executions, "chips": prof.chips,
            "scoped": prof.scoped, "busy_ms": prof.busy_ms,
            "busy_largest_chip_ms": prof.busy_max_ms,
            "gaps_ms": prof.gaps_ms, "launch_ms": prof.launch_ms,
            "drain_ms": prof.drain_ms,
            "lane_offset_ms": prof.lane_offset_ms,
            "operators": operator_rows(root, prof),
            "result_ms": prof.scope_ms(RESULT_SCOPE),
            "unattributed_ms": prof.unattributed_ms,
            "unattributed_ops": prof.unattributed_ops,
            "top_ops": prof.top_ops,
            "profile_s": prof.profile_s}


def render(root: Operator, prof: DeviceProfile) -> List[str]:
    """EXPLAIN ANALYZE (DEVICE)'s table."""
    busy = prof.busy_ms or 1.0

    def line(head: str, ms: float, tail: str = "") -> str:
        return (f"  {head:<52} {ms:9.3f} device-ms "
                f"({100.0 * ms / busy:5.1f}%){tail}")

    lines = ["device time by operator (self time of one execution):"]
    if not prof.scoped:
        lines.append("  scopes: none (executable compiled by a tree "
                     "without them)")
    for r in operator_rows(root, prof):
        kind = r["kind"] + ("." + r["part"] if r["part"] else "")
        head = f"op{r['n']} {kind} {r['label']}".rstrip()[:52]
        tail = (f"  [named {r['named_ms']:.3f}, "
                f"inferred {r['inferred_ms']:.3f}]" if r["device_ms"] else "")
        if prof.chips > 1 and r["device_ms"]:
            tail += f"  largest chip {r['largest_chip_ms']:.3f}"
        lines.append(line(head, r["device_ms"], tail))
    lines.append(line("result", prof.scope_ms(RESULT_SCOPE)))
    tail = ""
    if prof.unattributed_ops:
        tail = "  [" + ", ".join(
            f"{name} {ms:.3f}" for name, ms, _ in prof.unattributed_ops[:3]) \
            + "]"
    lines.append(line("unattributed", prof.unattributed_ms, tail))
    lines.append(
        f"device: busy {prof.busy_ms:.3f} ms, gaps {prof.gaps_ms:.3f} ms, "
        f"launch {prof.launch_ms:.3f} ms, drain {prof.drain_ms:.3f} ms, "
        f"lane offset {prof.lane_offset_ms:.3f} ms, "
        f"{prof.executions} executions, {prof.chips} "
        f"chip{'s' if prof.chips != 1 else ''}")
    return lines


# ------------------------------------------------- the served runners ----

def served_runner(op: Operator):
    """The whole-query runner that last served `op`'s tree: the
    distributed one where a mesh did (parallel/dist_flow.py leaves it on
    the root), else the fused one if it has dispatched; or None."""
    runner = getattr(op, "_dist_runner", None)
    if runner is None:
        runner = getattr(op, "_fused_runner", None)
        if runner is not None and runner._last_bound is None:
            runner = None
    return runner


def profile_prepared(catalog, repeats: int = 5) -> Dict[str, dict]:
    """A profile of every prepared statement of `catalog` whose tree a
    whole-query runner has served, at the runner's last binding:
    {fingerprint: as_dict()}."""
    from cockroach_tpu.sql.sqlstats import fingerprint

    shared = getattr(catalog, "shared_prepared", None)
    if shared is None:
        return {}
    with shared[1]:
        entries = list(shared[0].items())
    out = {}
    for sql, prep in entries:
        runner = served_runner(prep.op) if prep.op is not None else None
        prof = runner.device_profile(repeats) if runner is not None else None
        if prof is not None:
            out[fingerprint(sql)] = as_dict(prep.op, prof)
    return out
