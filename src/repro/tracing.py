"""The program's tracer: host spans, named counters and the map from a
compiled engine's device operations to its named stages.

One tracer per process, always on; there is no switch, and the program
runs the same code whether or not a profiler records it.

* **Spans** (:func:`span`, :func:`query`) record their name, start and
  end on ``time.perf_counter``, the span open around them on the same
  thread (``parent``) and a request id that every span of one query
  shares.  The last :data:`CAPACITY` closed spans are kept in a ring
  (:func:`snapshot`).  Each span is also entered as a
  ``jax.profiler.TraceAnnotation``, so a profiled run holds it on the
  device trace's clock; with no profiler running that costs nanoseconds.
* **Counters** (:func:`count`, :func:`record`, :func:`counter`) are
  named integers.  :func:`record` also adds its counts to the query open
  on the calling thread, so a query's device counters are read back
  with its span (``Span.counts``).
* **Stages** (:func:`map_stages`, :func:`stages`): the device engines
  mark their work with ``jax.named_scope`` (:data:`STAGES`); the scope
  reaches each HLO instruction's ``op_name`` metadata.  Reading the
  optimized HLO of an executable gives, for each instruction a device
  trace can show (``fusion.14``, ``bvss_pull.4``), the stage it belongs
  to.  Only that small map is kept.

Span names used by the program: ``repro.prepare`` (children
``repro.prepare.ordering``, ``.bvss``, ``.weights`` on a weighted graph,
``.engine``) and ``repro.query`` (children ``repro.query.admit``,
``.dispatch``, ``.wait``, ``.copy``, ``.unpermute``, ``.verify``).
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import re
import threading
import time
from typing import Mapping

import jax

#: closed spans kept in the ring
CAPACITY = 65536
#: the named device stages of the BFS level step, as ``jax.named_scope``
BFS_STAGES = ("bfs.expand", "bfs.update", "bfs.finalize")
#: those of the delta-stepping SSSP engine: gather and min-plus pull,
#: scatter-min, and the settle sweep / bucket advance / next frontier
SSSP_STAGES = ("sssp.expand", "sssp.update", "sssp.bucket")
STAGES = BFS_STAGES + SSSP_STAGES
QUERY = "repro.query"


@dataclasses.dataclass(slots=True)
class Span:
    name: str
    start: float
    end: float
    span_id: int
    parent: int | None
    request: int | None
    counts: dict[str, int] | None = None    # a query's device counters

    @property
    def seconds(self) -> float:
        return self.end - self.start


class _Opened:
    """One span while its block runs.  A class rather than a generator
    context: a query opens several spans, and this costs about half."""

    __slots__ = ("_tracer", "_span", "_note")

    def __init__(self, tracer: "Tracer", name: str, request: int | None,
                 counts: dict | None):
        stack = tracer._open()
        parent = stack[-1] if stack else None
        if request is None and parent is not None:
            request = parent.request
        self._tracer = tracer
        self._span = Span(name, 0.0, 0.0, next(tracer._ids),
                          parent.span_id if parent else None, request,
                          counts)
        self._note = jax.profiler.TraceAnnotation(name)

    def __enter__(self) -> Span:
        self._tracer._local.stack.append(self._span)
        self._note.__enter__()
        self._span.start = time.perf_counter()
        return self._span

    def __exit__(self, *exc) -> None:
        s = self._span
        s.end = time.perf_counter()
        self._note.__exit__(*exc)
        local = self._tracer._local
        local.stack.pop()
        local.last[s.name] = s
        self._tracer._spans.append(s)


class Tracer:
    def __init__(self, capacity: int = CAPACITY):
        self._spans: collections.deque[Span] = collections.deque(
            maxlen=capacity)
        self._ids = itertools.count(1)
        self._requests = itertools.count(1)
        self._local = threading.local()
        self._lock = threading.Lock()
        self._counters: collections.Counter[str] = collections.Counter()
        self._stages: dict[str, str] = {}

    def _open(self) -> list[Span]:
        """The spans open on this thread, innermost last."""
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
            self._local.last = {}
        return stack

    def last(self, name: str) -> Span | None:
        """The span ``name`` this thread closed last."""
        self._open()
        return self._local.last.get(name)

    def span(self, name: str, *, request: int | None = None) -> _Opened:
        """Record the enclosed ``with`` block as span ``name``, a child of
        the span open on this thread; it joins that span's request unless
        ``request`` names one."""
        return _Opened(self, name, request, None)

    def query(self) -> _Opened | contextlib.nullcontext[Span]:
        """The ``repro.query`` span of one query, under a new request id.
        Inside a query already open on this thread it opens nothing: the
        inner call is part of that query."""
        for s in self._open():
            if s.name == QUERY:
                return contextlib.nullcontext(s)
        return _Opened(self, QUERY, next(self._requests), {})

    def count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._counters[name] += n

    def record(self, counts: Mapping[str, int]) -> None:
        """Add ``counts`` to the counters and to the query open on this
        thread."""
        counts = {k: int(v) for k, v in counts.items()}
        with self._lock:
            self._counters.update(counts)
        for s in reversed(self._open()):
            if s.name == QUERY:
                for k, v in counts.items():
                    s.counts[k] = s.counts.get(k, 0) + v
                break

    def counter(self, name: str) -> int:
        with self._lock:
            return self._counters[name]

    def snapshot(self) -> list[Span]:
        """The closed spans in the ring, oldest first."""
        return list(self._spans)

    def map_stages(self, hlo_text: str) -> dict[str, str]:
        """Map the device operations of one compiled executable to their
        stages (:func:`stage_map`) and keep the map.  Instruction names
        are unique within an executable; where two executables share a
        name, the one mapped last wins."""
        found = stage_map(hlo_text)
        with self._lock:
            self._stages.update(found)
        return found

    def stages(self) -> dict[str, str]:
        with self._lock:
            return dict(self._stages)


# ---------------------------------------------------------------------------
# optimized HLO text -> stage of each scheduled instruction
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Instr:
    name: str
    opcode: str
    operands: list[str]
    calls: list[str]         # fused / applied computations
    control: list[str]       # while body/condition, conditional branches
    op_name: str


_HEADER = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(")
_INSTR = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_STAGE = re.compile("|".join(re.escape(s) for s in STAGES))
_CALLS = re.compile(r"\b(?:calls|to_apply)=%?([\w.\-]+)")
_CONTROL = re.compile(r"\b(?:body|condition|true_computation|"
                      r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_OPERAND = re.compile(r"%([\w.\-]+)")


def _closing(text: str, i: int) -> int:
    """Index of the parenthesis closing the one at ``text[i]``."""
    depth = 0
    for j in range(i, len(text)):
        depth += text[j] == "("
        depth -= text[j] == ")"
        if depth == 0:
            return j
    return len(text)


def _split_type(rest: str) -> str:
    """Drop the result type (a tuple type holds spaces) from the text
    after ``name = ``."""
    if rest.startswith("("):
        return rest[_closing(rest, 0) + 1:].lstrip()
    return rest.split(" ", 1)[1] if " " in rest else ""


def parse_hlo(text: str) -> tuple[dict[str, list[Instr]], str | None]:
    """The instructions of each computation of an HLO module's text, and
    the name of the entry computation."""
    comps: dict[str, list[Instr]] = {}
    entry, cur = None, None
    for line in text.splitlines():
        if not line.startswith(" "):
            m = _HEADER.match(line)
            cur = None
            if m and line.rstrip().endswith("{"):
                cur = comps.setdefault(m.group(1), [])
                if line.startswith("ENTRY"):
                    entry = m.group(1)
            continue
        m = _INSTR.match(line)
        if cur is None or m is None:
            continue
        body = _split_type(m.group(2))
        opcode, paren, _ = body.partition("(")
        start = len(opcode)
        operands = _OPERAND.findall(
            body[start:_closing(body, start)]) if paren else []
        control = _CONTROL.findall(body)
        for group in _BRANCHES.findall(body):
            control += _OPERAND.findall(group) or [
                c.strip() for c in group.split(",")]
        op = _OP_NAME.search(body)
        cur.append(Instr(m.group(1), opcode.strip(), operands,
                         _CALLS.findall(body), control,
                         op.group(1) if op else ""))
    return comps, entry


def scheduled(comps: dict[str, list[Instr]], root: str) -> list[str]:
    """``root`` and the computations it reaches through control flow
    (while bodies and conditions, conditional branches): those whose
    instructions run as device operations of their own."""
    out, todo = [], [root]
    while todo:
        c = todo.pop()
        if c in out or c not in comps:
            continue
        out.append(c)
        for ins in comps[c]:
            todo.extend(ins.control)
    return out


def _innermost(op_name: str) -> str | None:
    found = _STAGE.findall(op_name)
    return found[-1] if found else None


def _fused_stage(comps: dict[str, list[Instr]], ins: Instr) -> str | None:
    """The stage most instructions of a fused computation carry."""
    votes: collections.Counter[str] = collections.Counter()
    todo = list(ins.calls)
    seen: set[str] = set()
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for inner in comps[c]:
            st = _innermost(inner.op_name)
            if st:
                votes[st] += 1
            todo.extend(inner.calls)
    return votes.most_common(1)[0][0] if votes else None


def stage_map(hlo_text: str) -> dict[str, str]:
    """Instruction name -> stage for every scheduled instruction of an
    optimized HLO module that a stage can be found for.

    An instruction takes the innermost stage in its own ``op_name``,
    else the stage most of its fused instructions carry.  Inside a loop or
    branch, one that carries none (XLA made it: a copy, a rematerialised
    iota) takes the stage of an instruction that uses its result, else of
    one that makes an operand of it, in the same computation.  Work of the
    entry computation outside every stage (a loop's initial state) stays
    unmapped."""
    comps, entry = parse_hlo(hlo_text)
    out: dict[str, str] = {}
    for c in scheduled(comps, entry) if entry else []:
        instrs = comps[c]
        stage = {i.name: _innermost(i.op_name) or _fused_stage(comps, i)
                 for i in instrs}
        if c == entry:
            out.update((k, v) for k, v in stage.items() if v)
            continue
        users: dict[str, list[str]] = collections.defaultdict(list)
        for i in instrs:
            for o in i.operands:
                users[o].append(i.name)
        changed = True
        while changed:
            changed = False
            for i in instrs:
                if stage[i.name]:
                    continue
                near = [stage.get(u) for u in users[i.name]] + [
                    stage.get(o) for o in i.operands]
                near = [s for s in near if s]
                if near:
                    stage[i.name] = near[0]
                    changed = True
        out.update((k, v) for k, v in stage.items() if v)
    return out


_TRACER = Tracer()
span = _TRACER.span
query = _TRACER.query
count = _TRACER.count
record = _TRACER.record
counter = _TRACER.counter
snapshot = _TRACER.snapshot
last = _TRACER.last
map_stages = _TRACER.map_stages
stages = _TRACER.stages
