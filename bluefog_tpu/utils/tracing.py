"""Request-scoped tracing: Dapper-style spans with a flight-recorder cost.

The flight recorder answers "what did this *rank* just do"; the metrics
registry answers "is the fleet healthy".  Neither can answer the serving
question "where did *this request's* 180 ms go" — that needs spans keyed
by a trace id that follows one request across its lifecycle: admit →
queue-wait → prefill (prefix hit or cold) → each fused decode call →
spec-verify accept/reject → retire.  This module is that span store,
built to the same cost discipline as :mod:`bluefog_tpu.utils.flight`:

* the hot path (:func:`add_span`) is one module-global bool check when
  disarmed, and one dict build + one GIL-atomic ``deque.append`` when
  armed — lock-free, no device state touched, donation and the retrace
  sentinel untouched (pinned by ``tests/test_tracing.py``);
* jax is never imported — launcher children and tools read/write trace
  bundles for free;
* the ring is bounded (default 65536 spans, oldest dropped and counted).

Clock model: span endpoints are ``time.monotonic()`` — the same clock
the serve scheduler stamps ``submitted_at``/``finished_at`` with, so a
request's span tree and its measured E2E latency are directly
comparable.  Each rank's bundle carries one ``(monotonic, wall)`` anchor
pair so ``tools/trace_report.py`` can place every rank's spans on a
shared wall-clock axis when merging into Chrome-trace format.

Stage spans: :class:`stage` is the one way the program times a stage of
its own work as it happens.  Whenever a profiler collects it opens a
``jax.profiler.TraceAnnotation("bf:<cat>.<name>", **attrs)`` (it asks the
annotation's own first question, ``is_enabled()``, before it builds one:
with no session there is nothing to build): in any ``jax.profiler`` trace
it puts the stage on the host plane, on the device planes' clock, with its
entry-time attributes as the event's stats; nothing has to be armed for
that.  When the ring is armed it also records the same interval here,
under the plain ``name``.  The vocabulary (``bf:serve.step`` ⊃
``admit``/``prefill``/``pack``/``deliver``; ``bf:engine.<call>`` ⊃
``stage_in``/``dispatch``/``collect`` ⊃ ``wait``/``read_back``;
``bf:train.train_step`` ⊃ ``dispatch``) is tabled in
``docs/OBSERVABILITY.md``.  Spans whose
endpoints lie in the past (``queue``, ``request``, the per-rider
``decode``) cannot be annotations and stay :func:`add_span`, ring only.

The stage ring: every :class:`stage`, armed or not, profiler or not, also
stamps its entry and exit (``time.perf_counter_ns()``: on Linux the clock
of ``time.perf_counter()`` and ``time.monotonic()`` alike) into ONE bounded
ring of stage records, so that a stall in a window nobody traced still
names its stage: the interned ``bf:`` name, start, end, nesting depth, the
size that names the stage's bucket (``S``, ``Tpad`` or ``T`` of its entry
attributes) and, for an outermost stage while the per-request ring is
armed, the thread's CPU time across it (that clock is a system call: 5 to
6 us a reading on the chip machine's host, where the whole record costs
0.4).
Five preallocated flat arrays (:data:`STAGE_CAPACITY` records,
:data:`STAGE_RING_BYTES`) under a wrapping counter: a record is no object,
so the ring gives the garbage collector nothing to count.
:func:`stage_records` hands the records out, :func:`stage_dropped` counts
what the ring overwrote, and :func:`flush` writes them beside the request
bundle.  While the per-request ring is armed, a second observer (a daemon
thread that sleeps 10 ms at a time) writes a ``bf:host.pause`` record for
every wake that came more than 50 ms late: its ticks stop when the process
or the machine stands still, and go on while the driving thread alone
waits.

Device scopes: the DEVICE's time is named from inside the programs.  A
``jax.named_scope`` of :data:`DEVICE_SCOPES` (``ffn``, ``cache.read``,
``GRADIENT``, ...; tabled in ``docs/OBSERVABILITY.md``) reaches every
compiled instruction's ``op_name``; whoever compiles a program hands it to
:func:`register_program` (a kept reference, nothing else), and
:func:`device_scopes` parses, when asked, which instruction belongs to
which scope.  A device trace names instructions and nothing more, so a
reader of one (``perfbench/harness/scopes.py``) sums device time by scope
through that table, under names that survive a recompile's renumbering.

Arming: ``BLUEFOG_TRACE=<dir>`` (or :func:`configure`) arms recording
(the per-request ring; stage spans reach a profiler's trace without it)
and directs :func:`flush` to ``<dir>/trace_rank<r>.trace.jsonl`` — one
self-describing JSONL bundle per rank (a ``meta`` line, then one line
per span), written atomically and flushed again at exit — with the
device-scope tables of the programs compiled so far
(``<dir>/device_scopes_rank<r>.json``) and the stage ring
(``<dir>/stages_rank<r>.json``) beside it.  Producers:

* the serve scheduler threads request spans (``cat="serve"``) and tags
  each :class:`~bluefog_tpu.serve.scheduler.Request` with its trace id;
* the serve scheduler stages each step (``cat="serve"``, its own trace);
* the serve engine stages its device calls (``cat="engine"``);
* ``_InstrumentedStep`` stages the train step, its dispatch and the
  consensus probe (``cat="train"``).
"""
from __future__ import annotations

import itertools
import json
import numbers
import os
import re
import sys
import threading
import time
from array import array
from collections import deque
from typing import (Any, Callable, Dict, List, NamedTuple, Optional, Tuple,
                    Union)

from . import hlo_bytes
from .config import logger

__all__ = [
    "SCHEMA", "ENV_TRACE", "enabled", "configure", "maybe_enable_from_env",
    "new_trace", "add_span", "mark", "stage", "spans", "dropped",
    "flush", "bundle_path", "capacity", "reset", "DEVICE_SCOPES",
    "register_program", "device_scopes", "scope_of", "scopes_path",
    "STAGES_SCHEMA", "STAGE_CAPACITY", "STAGE_RING_BYTES", "PAUSE_NAME",
    "StageRecord", "stage_records", "stage_dropped", "stages_path",
]

SCHEMA = "bluefog-trace-1"
STAGES_SCHEMA = "bluefog-stages-1"
ENV_TRACE = "BLUEFOG_TRACE"
DEFAULT_CAPACITY = 65536
# the stage ring: the fastest serving cell's whole window and traced tail
# (200 steps a second of 12 to 20 stages, 33 s) twice over
STAGE_CAPACITY = 262144                     # a power of two: slot = seq & mask
_STAGE_MASK = STAGE_CAPACITY - 1
PAUSE_NAME = "bf:host.pause"
PAUSE_SLEEP_NS = 10_000_000                 # the second observer's sleep
PAUSE_LATE_NS = 50_000_000                  # a wake this late is a pause

_armed = False                   # the one hot-path gate
_dir: Optional[str] = None
_buf: deque = deque(maxlen=DEFAULT_CAPACITY)
_seq = itertools.count(1)
_last_seq = 0
_trace_seq = itertools.count(1)
_atexit_registered = False
_annotation = None               # jax.profiler.TraceAnnotation, once jax is in

# the stage ring's columns, one slot a record (zeroed pages, touched as the
# ring fills): nothing here is an object the collector tracks
_sr_name = array("h", bytes(2 * STAGE_CAPACITY))    # id in _stage_names
_sr_depth = array("b", bytes(STAGE_CAPACITY))       # stages open around it
_sr_t0 = array("q", bytes(8 * STAGE_CAPACITY))      # perf_counter_ns
_sr_t1 = array("q", bytes(8 * STAGE_CAPACITY))
_sr_cpu = array("q", bytes(8 * STAGE_CAPACITY))     # thread CPU ns, depth 0
#                                                     (-1: not armed)
STAGE_RING_BYTES = sum(a.itemsize * len(a) for a in (
    _sr_name, _sr_depth, _sr_t0, _sr_t1, _sr_cpu))
# records ever written; slot = this & mask.  Two threads that write at once
# (the pause observer is the one other writer) can lose a record, no more
_sr_written = 0
_depth = 0                       # stages open now (one driving thread)
_UNNAMED = "bf:trace.unnamed"    # the 32,768th name and every later one
_stage_ids: Dict[Union[str, Tuple[str, Optional[int]]], int] = {_UNNAMED: 0}
_stage_names: List[Tuple[str, Optional[int]]] = [(_UNNAMED, None)]
_intern_lock = threading.Lock()
_now_ns = time.perf_counter_ns
_cpu_ns = time.thread_time_ns
_pause_stop: Optional[threading.Event] = None


def enabled() -> bool:
    """True when spans are being recorded."""
    return _armed


def capacity() -> int:
    return _buf.maxlen if _buf.maxlen is not None else 0


def configure(out_dir: Optional[str], capacity: Optional[int] = None) -> None:
    """Arm recording (``out_dir=None`` disarms without dropping spans).

    ``capacity`` resizes the span ring, keeping the newest spans."""
    global _armed, _dir, _buf, _atexit_registered
    if capacity is not None:
        if capacity < 1:
            raise ValueError(f"trace capacity must be >= 1, got {capacity}")
        _buf = deque(_buf, maxlen=int(capacity))
    _dir = out_dir
    _armed = out_dir is not None
    _watch_pauses(_armed)
    if _armed and not _atexit_registered:
        import atexit
        atexit.register(_final_flush)
        _atexit_registered = True


def maybe_enable_from_env() -> bool:
    """Honor ``BLUEFOG_TRACE=<dir>`` at init (the tracing analogue of the
    flight/metrics/timeline env hooks).  Returns True when armed."""
    out_dir = os.environ.get(ENV_TRACE)
    if not out_dir:
        return False
    configure(out_dir)
    return True


# ---------------------------------------------------------------------------
# Recording (the lock-free hot path)
# ---------------------------------------------------------------------------

def new_trace(kind: str = "req", key: Optional[Any] = None) -> str:
    """Mint a process-unique trace id: ``"<kind>-r<rank>-<n>"``.

    Deterministic (a per-process counter, no RNG) so replays produce
    stable ids; ``key`` overrides the counter when the caller already
    has a natural id (the scheduler passes the request id)."""
    n = key if key is not None else next(_trace_seq)
    return f"{kind}-r{_rank()}-{n}"


def add_span(trace: str, name: str, t0: float, t1: float, *,
             cat: str = "", parent: Optional[int] = None,
             **attrs: Any) -> int:
    """Record one completed span; returns its span id (0 when disarmed).

    ``t0``/``t1`` are ``time.monotonic()`` endpoints measured by the
    caller — the recorder never injects its own clock reads into the
    middle of a hot loop.  Extra keyword attrs ride the span verbatim.
    """
    global _last_seq
    if not _armed:
        return 0
    sid = next(_seq)
    ev: Dict[str, Any] = {"kind": "span", "seq": sid, "trace": trace,
                          "span": sid, "name": name, "t0": t0, "t1": t1}
    if cat:
        ev["cat"] = cat
    if parent:
        ev["parent"] = parent
    if attrs:
        ev.update(attrs)
    _last_seq = sid
    _buf.append(ev)
    return sid


def mark(trace: str, name: str, *, cat: str = "",
         parent: Optional[int] = None, **attrs: Any) -> int:
    """Instant event (zero-duration span) at now."""
    t = time.monotonic()
    return add_span(trace, name, t, t, cat=cat, parent=parent, **attrs)


class stage:
    """``with tracing.stage(trace, "decode_call", cat="engine", S=32):`` —
    a stage of the program's own work, timed as it happens.

    A ``bf:<cat>.<name>`` annotation in any profiler's trace (the keyword
    attrs, known on entry, become the event's stats), and always a record
    in the stage ring (:func:`stage_records`); in a process that never
    imported jax a shell that still records.  Armed, the same
    interval also lands in the per-request ring as ``name`` with those
    attrs plus whatever the block added to ``.attrs``."""

    __slots__ = ("trace", "name", "cat", "parent", "attrs", "_ann", "_t0",
                 "_id", "_cpu0", "_ns0")

    def __init__(self, trace: str, name: str, *, cat: str,
                 parent: Optional[int] = None, **attrs: Any):
        self.trace, self.name, self.cat = trace, name, cat
        self.parent, self.attrs = parent, attrs

    def __enter__(self) -> "stage":
        global _annotation, _depth
        if _annotation is None:
            jax = sys.modules.get("jax")
            _annotation = jax.profiler.TraceAnnotation if jax else False
        full = f"bf:{self.cat}.{self.name}"
        a = self.attrs
        # the annotation's own first question, asked before one is built:
        # with no profiler collecting there is nothing to build
        self._ann = _annotation and _annotation.is_enabled() \
            and _annotation(full, **a)
        if self._ann:
            self._ann.__enter__()
        self._t0 = time.monotonic() if _armed else None
        if a:
            # a stage with a bucket is interned with it: one id says both
            full = (full, a["S"] if "S" in a else a["Tpad"] if "Tpad" in a
                    else a.get("T"))
        sid = _stage_ids.get(full)
        self._id = _intern(full) if sid is None else sid
        if not _depth:
            # the thread's CPU clock is a system call, 5 to 6 us a reading
            # on the chip machine's host: at the edges of an outermost
            # stage alone, and only while the per-request ring is armed
            self._cpu0 = _cpu_ns() if _armed else -1
        _depth += 1
        self._ns0 = _now_ns()
        return self

    def __exit__(self, *exc) -> None:
        # the ring's write is spelled out here (as _stage_write has it):
        # a call would cost a tenth of the stage
        global _depth, _sr_written
        t1 = _now_ns()
        d = _depth = _depth - 1
        i = _sr_written
        _sr_written = i + 1
        i &= _STAGE_MASK
        _sr_t0[i] = self._ns0
        _sr_t1[i] = t1
        _sr_name[i] = self._id
        _sr_depth[i] = d if d < 128 else 127
        if not d:
            c = self._cpu0
            _sr_cpu[i] = -1 if c < 0 else _cpu_ns() - c
        if self._t0 is not None and _armed:
            add_span(self.trace, self.name, self._t0, time.monotonic(),
                     cat=self.cat, parent=self.parent, **self.attrs)
        if self._ann:
            self._ann.__exit__(*exc)


# ---------------------------------------------------------------------------
# The stage ring: every stage's interval, with nothing armed
# ---------------------------------------------------------------------------

class StageRecord(NamedTuple):
    """One record of the stage ring; times in seconds on the clock of
    ``time.perf_counter()``."""
    name: str                    # "bf:<cat>.<name>"
    bucket: Optional[int]        # S, Tpad or T of the entry attributes
    t0: float
    t1: float
    depth: int                   # stages open around it (0: outermost)
    cpu_s: Optional[float]       # thread CPU time across it (depth 0, armed)


def _intern(key: Union[str, Tuple[str, Optional[int]]]) -> int:
    """The small id of a stage's name, or of its name and bucket."""
    with _intern_lock:
        sid = _stage_ids.get(key)
        if sid is None:
            sid = len(_stage_names)
            if sid > 32767:                     # what the column holds
                return _stage_ids[_UNNAMED]
            name, bucket = key if isinstance(key, tuple) else (key, None)
            _stage_names.append((name, int(bucket) if isinstance(
                bucket, numbers.Integral) else None))
            _stage_ids[key] = sid
    return sid


def _stage_write(sid: int, t0: int, t1: int) -> None:
    """One record of an interval that is no stage's (the pause observer's):
    depth 0, no CPU time.  :meth:`stage.__exit__` spells the same write
    out for itself."""
    global _sr_written
    i = _sr_written
    _sr_written = i + 1
    i &= _STAGE_MASK
    _sr_t0[i] = t0
    _sr_t1[i] = t1
    _sr_name[i] = sid
    _sr_depth[i] = 0
    _sr_cpu[i] = -1


def stage_records(since: Optional[float] = None) -> List[StageRecord]:
    """The stage ring's records, oldest first (the order stages ENDED in);
    with ``since`` (seconds on ``time.perf_counter()``) those that ended at
    or after it.  Builds its tuples when asked: call it after the window,
    not inside."""
    n = _sr_written
    cut = None if since is None else int(since * 1e9)
    out = []
    for i in range(max(0, n - STAGE_CAPACITY), n):
        k = i & _STAGE_MASK
        if cut is not None and _sr_t1[k] < cut:
            continue
        depth, cpu = _sr_depth[k], _sr_cpu[k]
        out.append(StageRecord(
            *_stage_names[_sr_name[k]], _sr_t0[k] / 1e9, _sr_t1[k] / 1e9,
            depth, None if depth or cpu < 0 else cpu / 1e9))
    return out


def stage_dropped() -> int:
    """Records the stage ring has overwritten."""
    return max(0, _sr_written - STAGE_CAPACITY)


def _pause_loop(stop: threading.Event) -> None:
    """The second observer: asleep :data:`PAUSE_SLEEP_NS` at a time, and a
    ``bf:host.pause`` record, from when the wake was due until it came,
    for every wake more than :data:`PAUSE_LATE_NS` late."""
    sid = _intern(PAUSE_NAME)
    last = _now_ns()
    while not stop.wait(PAUSE_SLEEP_NS / 1e9):
        now = _now_ns()
        due = last + PAUSE_SLEEP_NS
        if now - due > PAUSE_LATE_NS:
            _stage_write(sid, due, now)
        last = now


def _watch_pauses(on: bool) -> None:
    """The pause observer lives exactly while the ring is armed."""
    global _pause_stop
    if on and _pause_stop is None:
        _pause_stop = threading.Event()
        threading.Thread(target=_pause_loop, args=(_pause_stop,),
                         name="bf-trace-pause", daemon=True).start()
    elif not on and _pause_stop is not None:
        _pause_stop.set()
        _pause_stop = None


def stages_path(out_dir: Optional[str] = None) -> str:
    """Where :func:`flush` writes this rank's stage ring."""
    base = out_dir if out_dir is not None else (_dir or ".")
    return os.path.join(base, f"stages_rank{_rank()}.json")


# ---------------------------------------------------------------------------
# Device scopes: which instruction of a compiled program is which named part
# ---------------------------------------------------------------------------

# the one vocabulary (docs/OBSERVABILITY.md tables where each is opened
# and which metric reads it)
DEVICE_SCOPES = frozenset((
    "GRADIENT", "ADAPT", "COMMUNICATE", "STATE_SYNC",      # the train step
    "attn", "attn.project", "attn.window", "attn.full",
    "mla.project", "mla.attend", "cache.read", "cache.write",
    "ffn", "moe.route", "moe.experts", "moe.shared", "moe.zero", "readout",
    "hc.coef", "hc.mix",                    # the residual streams' maps
    # a state-space mixer: its two projections with the gate and grouped
    # norm, its convolution, its scan or single step with the state's read
    # and write; and an expert layer's two latent projections
    "ssm.project", "ssm.conv", "ssm.scan", "moe.latent"))

_NOT_A_SCOPE_RE = re.compile(r"\bp?jit\([^()]*\)")     # a function's name
_PATH_NAME_RE = re.compile(r"[^/()]+")
_programs: Dict[str, Any] = {}
_tables: Dict[str, dict] = {}


def register_program(key: str,
                     compiled: Union[Any, Callable[[], Any]]) -> None:
    """Keep the compiled program ``key`` (``"decode S=32"``, ``"prefill
    Tpad=256"``, ``"train_step"``) for :func:`device_scopes`: the object
    ``jit(f).lower(...).compile()`` returns, or a function that makes it
    when asked.  A kept reference and nothing else: no text is made and
    nothing is parsed until someone asks.  A key registered again (another
    engine in the process) replaces the earlier program."""
    _programs[key] = compiled
    _tables.pop(key, None)


def scope_of(op_name: str) -> Tuple[str, str]:
    """``(scope, direction)`` of an instruction's ``op_name``: the
    INNERMOST name of :data:`DEVICE_SCOPES` on its path (``""`` if none)
    and ``"bwd"`` under a ``transpose(``, ``"fwd"`` under a ``jvp(``
    alone, ``""`` outside differentiation.  A rematerialised forward is
    traced under the transpose and counts as ``bwd``: it runs there."""
    found = [t for t in _PATH_NAME_RE.findall(
        _NOT_A_SCOPE_RE.sub("", op_name)) if t in DEVICE_SCOPES]
    return (found[-1] if found else "",
            "bwd" if "transpose(" in op_name
            else "fwd" if "jvp(" in op_name else "")


def _scope_table(hlo_txt: str) -> dict:
    """One program's table (:func:`device_scopes`) from its compiled
    text."""
    module, comps = hlo_bytes.op_names(hlo_txt)
    ops, mixed, inherited = {}, {}, {}
    for rows in comps.values():
        users: Dict[str, list] = {}
        for name, (opcode, op_name, fused, reads) in rows.items():
            ops[name] = scope_of(op_name)
            held = {scope_of(f) for f in fused}
            inside = {scope for scope, _ in held} - {""}
            if len(inside) > 1:
                mixed[name] = tuple(sorted(inside))
            elif inside and not ops[name][0]:
                # a root outside every scope (the step's re-stacking of
                # its outputs behind the optimizer update) over fused
                # instructions that all lie in one
                directions = {d for scope, d in held if scope}
                ops[name] = (inside.pop(), directions.pop()
                             if len(directions) == 1 else "")
                inherited[name] = "fused"
            for r in reads:
                users.setdefault(r, []).append(name)

        def around(name, links, seen):
            """The scoped instructions ``name`` reaches along ``links``,
            through what only forwards a buffer."""
            for n in links(name):
                if n in seen:
                    continue
                seen.add(n)
                if ops[n][0]:
                    yield ops[n]
                elif rows[n][0] in hlo_bytes._FORWARDING:
                    yield from around(n, links, seen)

        # what the compiler made itself, or traced outside every scope
        # (a scan's slice of its layer's weights, a kernel the compiler
        # expands an op into): the part its operands come from, else the
        # part that reads it, where they agree; operands first, in program
        # order, so a chain of such instructions resolves in one pass
        for how, links in (("operands", lambda n: rows[n][3]),
                           ("users", lambda n: users.get(n, ()))):
            for name, (opcode, *_) in rows.items():
                if ops[name][0] or opcode in hlo_bytes._FORWARDING:
                    continue
                found = set(around(name, links, {name}))
                if len({scope for scope, _ in found}) == 1:
                    scope, direction = found.pop()
                    ops[name] = (scope, "" if found else direction)
                    inherited[name] = how
    return {"module": module, "ops": ops, "mixed": mixed,
            "inherited": inherited}


def device_scopes() -> Dict[str, dict]:
    """Per registered program ``{"module": the HLO module's name, "ops":
    {instruction: (scope, direction)}, "mixed": {fusion: (scopes, ...)},
    "inherited": {instruction: "fused" | "operands" | "users"}}``.  ``ops`` holds
    every instruction a device trace can show (fused interiors apart); a
    fusion goes by its root.  ``mixed`` lists the fusions whose fused
    instructions lie in more than one scope, with those scopes: their
    time is their root's in ``ops``, and a reader can say how much time
    that is.  ``inherited`` lists the instructions that carry no scope of
    their own and were given one: a fusion the one scope its fused
    instructions lie in, any other the one all its scoped operands (else
    all its users) lie in.  Parsed from ``compiled.as_text()`` on the first
    call that finds the program, kept after."""
    for key, compiled in list(_programs.items()):
        if key not in _tables:
            if not hasattr(compiled, "as_text"):
                compiled = _programs[key] = compiled()
            _tables[key] = _scope_table(compiled.as_text())
    return {key: _tables[key] for key in _programs}


def scopes_path(out_dir: Optional[str] = None) -> str:
    base = out_dir if out_dir is not None else (_dir or ".")
    return os.path.join(base, f"device_scopes_rank{_rank()}.json")


# ---------------------------------------------------------------------------
# Introspection + bundles
# ---------------------------------------------------------------------------

def spans() -> List[dict]:
    """Snapshot of the buffered spans, oldest first."""
    return list(_buf)


def dropped() -> int:
    return max(0, _last_seq - len(_buf))


def _rank() -> int:
    jax = sys.modules.get("jax")
    if jax is not None:
        try:
            return jax.process_index()
        except Exception:
            pass
    try:
        return int(os.environ.get("BLUEFOG_PROCESS_ID", "0"))
    except ValueError:
        return 0


def bundle_path(out_dir: Optional[str] = None) -> str:
    base = out_dir if out_dir is not None else (_dir or ".")
    return os.path.join(base, f"trace_rank{_rank()}.trace.jsonl")


def _write_atomic(to: str, dump: Callable[[Any], None]) -> None:
    tmp = f"{to}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        dump(f)
    os.replace(tmp, to)


def flush(path: Optional[str] = None) -> str:
    """Write the span ring as a per-rank JSONL bundle; returns the path.

    Line 1 is the ``meta`` record (schema, rank, the monotonic↔wall
    anchor the merger aligns ranks with, drop count); every further line
    is one span.  The whole file is rewritten atomically on each flush —
    the ring holds the newest spans either way.  Beside it go the device
    scopes' tables and, where a directory is armed, the stage ring
    (:func:`stages_path`).
    """
    if path is None:
        path = bundle_path()
    snap = list(_buf)
    meta = {"kind": "meta", "schema": SCHEMA, "rank": _rank(),
            "pid": os.getpid(), "mono": time.monotonic(),
            "wall": time.time(), "n_spans": len(snap),
            "dropped": max(0, _last_seq - len(snap))}
    perf = time.perf_counter()
    beside = os.path.dirname(path) or "."
    os.makedirs(beside, exist_ok=True)
    _write_atomic(path, lambda f: f.writelines(
        json.dumps(ev) + "\n" for ev in [meta] + snap))
    if _programs:
        # beside the bundle: with a jax.profiler trace of the same
        # process, device time splits by scope offline
        _write_atomic(scopes_path(beside), lambda f: json.dump(
            {"schema": SCHEMA, "rank": meta["rank"],
             "programs": device_scopes()}, f))
    if _dir is not None and _sr_written:
        # the stage ring, for tools/trace_report.py: records as [index
        # into names, bucket, start, end, depth, cpu_s], seconds on
        # time.perf_counter ("perf": that clock at the "wall" above)
        names: Dict[str, int] = {}
        _write_atomic(stages_path(beside), lambda f: json.dump(
            {"schema": STAGES_SCHEMA, "rank": meta["rank"],
             "pid": meta["pid"], "wall": meta["wall"],
             "perf": perf, "capacity": STAGE_CAPACITY,
             "dropped": stage_dropped(),
             "records": [[names.setdefault(r.name, len(names)), *r[1:]]
                         for r in stage_records()],
             "names": list(names)}, f))
    return path


def _final_flush() -> None:
    if _armed:
        try:
            flush()
        except Exception:                                 # pragma: no cover
            logger.warning("trace flush at exit failed", exc_info=True)


def reset() -> None:
    """Test isolation: disarm, drop every buffered span, stage record and
    program."""
    global _armed, _dir, _buf, _seq, _last_seq, _trace_seq
    global _sr_written, _depth
    _programs.clear()
    _tables.clear()
    _armed = False
    _dir = None
    _watch_pauses(False)
    _sr_written = _depth = 0
    _buf = deque(maxlen=DEFAULT_CAPACITY)
    _seq = itertools.count(1)
    _last_seq = 0
    _trace_seq = itertools.count(1)
