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
its own work as it happens.  It always opens a
``jax.profiler.TraceAnnotation("bf:<cat>.<name>", **attrs)`` — free
when no profiler session runs, and in any ``jax.profiler`` trace it puts
the stage on the host plane, on the device planes' clock, with its
entry-time attributes as the event's stats; nothing has to be armed for
that.  When the ring is armed it also records the same interval here,
under the plain ``name``.  The vocabulary (``bf:serve.step`` ⊃
``admit``/``prefill``/``pack``/``deliver``; ``bf:engine.<call>`` ⊃
``stage_in``/``dispatch``/``collect``;
``bf:train.train_step`` ⊃ ``dispatch``) is tabled in
``docs/OBSERVABILITY.md``.  Spans whose
endpoints lie in the past (``queue``, ``request``, the per-rider
``decode``) cannot be annotations and stay :func:`add_span`, ring only.

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
device-scope tables of the programs compiled so far beside it
(``<dir>/device_scopes_rank<r>.json``).  Producers:

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
import os
import re
import sys
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

from . import hlo_bytes
from .config import logger

__all__ = [
    "SCHEMA", "ENV_TRACE", "enabled", "configure", "maybe_enable_from_env",
    "new_trace", "add_span", "mark", "span", "stage", "spans", "dropped",
    "flush", "bundle_path", "capacity", "reset", "DEVICE_SCOPES",
    "register_program", "device_scopes", "scope_of", "scopes_path",
]

SCHEMA = "bluefog-trace-1"
ENV_TRACE = "BLUEFOG_TRACE"
DEFAULT_CAPACITY = 65536

_armed = False                   # the one hot-path gate
_dir: Optional[str] = None
_buf: deque = deque(maxlen=DEFAULT_CAPACITY)
_seq = itertools.count(1)
_last_seq = 0
_trace_seq = itertools.count(1)
_atexit_registered = False
_annotation = None               # jax.profiler.TraceAnnotation, once jax is in


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


class span:
    """``with tracing.span(trace, "gossip", cat="train"): ...`` — times
    the block and records one span on exit (attrs may be added to
    ``.attrs`` inside the block).  Zero-cost shell when disarmed."""

    __slots__ = ("trace", "name", "cat", "parent", "attrs", "_t0", "id")

    def __init__(self, trace: str, name: str, *, cat: str = "",
                 parent: Optional[int] = None, **attrs: Any):
        self.trace, self.name, self.cat = trace, name, cat
        self.parent, self.attrs = parent, attrs
        self._t0 = 0.0
        self.id = 0

    def __enter__(self) -> "span":
        if _armed:
            self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> None:
        if _armed:
            self.id = add_span(self.trace, self.name, self._t0,
                               time.monotonic(), cat=self.cat,
                               parent=self.parent, **self.attrs)


class stage:
    """``with tracing.stage(trace, "decode_call", cat="engine", S=32):`` —
    a stage of the program's own work, timed as it happens.

    Always a ``bf:<cat>.<name>`` annotation in the profiler's trace (the
    keyword attrs, known on entry, become the event's stats); a no-op
    shell in a process that never imported jax.  Armed, the same interval
    also lands in the ring as ``name`` with those attrs plus whatever the
    block added to ``.attrs``."""

    __slots__ = ("trace", "name", "cat", "parent", "attrs", "_ann", "_t0")

    def __init__(self, trace: str, name: str, *, cat: str,
                 parent: Optional[int] = None, **attrs: Any):
        self.trace, self.name, self.cat = trace, name, cat
        self.parent, self.attrs = parent, attrs

    def __enter__(self) -> "stage":
        global _annotation
        if _annotation is None:
            jax = sys.modules.get("jax")
            _annotation = jax.profiler.TraceAnnotation if jax else False
        self._ann = _annotation and _annotation(
            f"bf:{self.cat}.{self.name}", **self.attrs)
        if self._ann:
            self._ann.__enter__()
        self._t0 = time.monotonic() if _armed else None
        return self

    def __exit__(self, *exc) -> None:
        if self._t0 is not None and _armed:
            add_span(self.trace, self.name, self._t0, time.monotonic(),
                     cat=self.cat, parent=self.parent, **self.attrs)
        if self._ann:
            self._ann.__exit__(*exc)


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


def flush(path: Optional[str] = None) -> str:
    """Write the span ring as a per-rank JSONL bundle; returns the path.

    Line 1 is the ``meta`` record (schema, rank, the monotonic↔wall
    anchor the merger aligns ranks with, drop count); every further line
    is one span.  The whole file is rewritten atomically on each flush —
    the ring holds the newest spans either way.
    """
    if path is None:
        path = bundle_path()
    snap = list(_buf)
    meta = {"kind": "meta", "schema": SCHEMA, "rank": _rank(),
            "pid": os.getpid(), "mono": time.monotonic(),
            "wall": time.time(), "n_spans": len(snap),
            "dropped": max(0, _last_seq - len(snap))}
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        f.write(json.dumps(meta) + "\n")
        for ev in snap:
            f.write(json.dumps(ev) + "\n")
    os.replace(tmp, path)
    if _programs:
        # beside the bundle: with a jax.profiler trace of the same
        # process, device time splits by scope offline
        beside = scopes_path(os.path.dirname(path) or ".")
        tmp = f"{beside}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({"schema": SCHEMA, "rank": meta["rank"],
                       "programs": device_scopes()}, f)
        os.replace(tmp, beside)
    return path


def _final_flush() -> None:
    if _armed:
        try:
            flush()
        except Exception:                                 # pragma: no cover
            logger.warning("trace flush at exit failed", exc_info=True)


def reset() -> None:
    """Test isolation: disarm, drop every buffered span and program."""
    global _armed, _dir, _buf, _seq, _last_seq, _trace_seq
    _programs.clear()
    _tables.clear()
    _armed = False
    _dir = None
    _buf = deque(maxlen=DEFAULT_CAPACITY)
    _seq = itertools.count(1)
    _last_seq = 0
    _trace_seq = itertools.count(1)
