"""Continuous batching: admit/retire requests between decode steps.

The scheduler owns everything dynamic so the engine can stay static: a
FIFO admission queue, one :class:`~.kv_cache.SlotAllocator` per replica,
a :class:`~.kv_cache.PrefixCache` per replica when prefix sharing is
armed, and the per-request token state.  Each :meth:`Scheduler.step` does

1. **admit** — pop queued requests into free slots.  With prefix pages
   armed, each prompt first probes its replica's prefix directory: a hit
   attaches the sealed page by reference and prefills ONLY the divergent
   remainder (one chunk call); a shareable miss seals the prefix into a
   reserved page on the way in, so the next request with the same system
   prompt hits.  Cold prompts take the plain one-prefill path.
2. **decode** — one fused engine call for ALL replicas at the smallest
   declared batch bucket that fits the busiest replica, idle lanes padded
   with the trash slot.  With ``spec_decode=k`` armed this is one
   speculative round (draft + verify) and each lane advances by its own
   accepted count; otherwise it is ``decode_steps_per_call`` plain steps,
   dispatched ONE CALL AHEAD of the host: this step's call goes out before
   the call of the step before has been read back (:meth:`Scheduler.step`).
3. **deliver** — the tokens of the call just read go to their requests;
   those that hit ``max_new_tokens`` (or the KV-cache length ceiling) free
   their slot, release their prefix page reference, and close their
   latency clocks.

Because admission only changes *which slot/page ids* ride in the bucketed
arrays — never a shape — steady-state traffic re-runs the warmed programs
and the retrace sentinel stays 0 with all three fast paths armed.

Request metrics ride the existing registry (JSONL/Prometheus exporters
and ``tools/metrics_report.py`` pick them up with no schema changes):
``bluefog_requests_total{status=...}``, ``bluefog_tokens_generated_total``,
the ``bluefog_serve_token_latency_seconds`` histogram (p50/p99 via
``histogram().percentile``), and the paired
``bluefog_serve_ttft_{hit,cold}_seconds`` histograms (time to first
token with and without a prefix hit).  A ``serve`` flight-bundle block
(:func:`bluefog_tpu.utils.flight.register_block`) carries the last
request ids per replica plus the resident prefix pages so
``tools/postmortem.py`` can blame the replica that died mid-stream.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Sequence

import numpy as np

from ..utils import fleetview as _fleetview
from ..utils import flight as _flight
from ..utils import metrics as _metrics
from ..utils import timeseries as _ts
from ..utils import tracing as _tracing
from .engine import ServeEngine
from .kv_cache import PrefixCache, SlotAllocator

__all__ = ["Request", "Scheduler", "AutoScaler"]

LATENCY_BUCKETS = (.0005, .001, .0025, .005, .01, .025, .05, .1, .25, .5,
                   1.0, 2.5)


@dataclasses.dataclass
class Request:
    """One generation request and its full lifecycle state."""
    id: int
    prompt: List[int]
    max_new_tokens: int
    state: str = "queued"            # queued -> running -> done | failed
    replica: int = -1
    slot: int = -1
    prefix_row: int = -1             # sealed page this request reads through
    prefix_len: int = 0              # tokens served by that page
    generated: List[int] = dataclasses.field(default_factory=list)
    submitted_at: float = 0.0
    admitted_at: Optional[float] = None
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    requeued: int = 0                # replica-failure evictions survived
    requeued_at: Optional[float] = None   # last eviction time (queue spans)
    trace_id: str = ""               # request-scoped trace (utils.tracing)
    flying: int = 0                  # tokens dispatched and not yet read

    @property
    def next_pos(self) -> int:
        """KV position the pending token will occupy: the last one
        generated, or chosen on the device by the call in flight."""
        return len(self.prompt) + len(self.generated) + self.flying - 1

    @property
    def ttft(self) -> Optional[float]:
        if self.first_token_at is None:
            return None
        return self.first_token_at - self.submitted_at


class Scheduler:
    """Continuous batching over one :class:`ServeEngine`."""

    def __init__(self, engine: ServeEngine):
        self.engine = engine
        self.replicas = engine.m.dp
        self._queue: Deque[Request] = deque()
        self._alloc = [SlotAllocator(engine.scfg.slots, replica=r)
                       for r in range(self.replicas)]
        scfg = engine.scfg
        self._prefix: List[Optional[PrefixCache]] = [
            PrefixCache(scfg.prefix_pages, scfg.prefix_page_tokens,
                        first_row=scfg.slots, replica=r)
            if scfg.prefix_pages else None
            for r in range(self.replicas)]
        self._active: List[Dict[int, Request]] = [
            {} for _ in range(self.replicas)]
        self._dead: set = set()
        self._parked: set = set()        # autoscale-parked subset of _dead
        self._next_id = 0
        self._last_ids: List[List[int]] = [[] for _ in range(self.replicas)]
        self.completed: List[Request] = []
        self.failed: List[Request] = []
        self.requeued_total = 0
        self._decode_calls = 0
        self._flying = None              # (lanes, t0) of the call in flight
        self._moe_load = None            # last ServeEngine.moe_load() snapshot
        self._slo = None                 # diagnostics.SLOEngine, if attached
        self._sched_trace = _tracing.new_trace("sched")
        _flight.register_block("serve", self._flight_block)

    # ------------------------------------------------------------------

    def submit(self, prompt: Sequence[int], max_new_tokens: int = 8,
               now: Optional[float] = None) -> Request:
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        # reject unservable prompts at submit, not mid-stream
        self.engine.scfg.prefill_bucket_for(len(prompt))
        req = Request(id=self._next_id, prompt=list(prompt),
                      max_new_tokens=max_new_tokens,
                      submitted_at=time.monotonic() if now is None else now)
        self._next_id += 1
        # process-global counter, not req.id: several schedulers can live in
        # one process (probe drains, benches) and each restarts ids at 0 —
        # keyed ids would collide and merge span trees across requests
        req.trace_id = _tracing.new_trace("req")
        _tracing.mark(req.trace_id, "submit", cat="serve", req=req.id,
                      prompt_len=len(req.prompt),
                      max_new_tokens=req.max_new_tokens)
        self._queue.append(req)
        return req

    @property
    def pending(self) -> int:
        return len(self._queue)

    @property
    def in_flight(self) -> int:
        return sum(len(a) for a in self._active)

    @property
    def done(self) -> bool:
        return (not self._queue and self.in_flight == 0
                and self._flying is None)

    def live_replicas(self) -> List[int]:
        return [r for r in range(self.replicas) if r not in self._dead]

    # ------------------------------------------------------------------

    def fail_replica(self, replica: int, reason: str = "failed",
                     park: bool = False) -> List[Request]:
        """Take a replica out of rotation (chaos kill / health eviction /
        autoscale retire).

        Its in-flight requests are NOT lost: their KV — and any shared
        prefix pages — lived on the dead slice, so each one is reset to
        its prompt and requeued at the HEAD of the admission queue (it
        already waited its turn once) with ``requeued`` stamped into the
        request and ``bluefog_requests_total{status="requeued"}``
        counted.  That label is per-EVENT, not per-request: a request
        evicted twice is counted twice, so ``requeued`` does not sum with
        the terminal ``done``/``failed`` statuses.  Re-delivery caveat
        for streaming consumers: ``generated`` is cleared because the KV
        behind it died, so tokens already streamed to a client are
        produced again when the request re-runs — dedupe on request id
        downstream if exactly-once token delivery matters.

        ``park=True`` marks this an autoscale park/retire: the slice
        stays alive (its engine state — KV pages, sealed prefixes — is
        intact, merely unscheduled), so :meth:`restore_replica` may
        re-admit traffic to it as-is.  Chaos kills and health evictions
        must leave ``park=False``: their backing slice is gone.
        """
        if replica in self._dead:
            return []
        self._dead.add(replica)
        if park:
            self._parked.add(replica)
        lost = list(self._active[replica].values())
        if self._flying is not None:
            # what the call in flight chose for these lanes is dropped
            # with their KV: the slots may have new owners when it is read
            self._flying[0][replica] = []
        for req in lost:
            self._alloc[replica].free(req.slot)
            if req.prefix_row >= 0 and self._prefix[replica] is not None:
                self._prefix[replica].release(req.prefix_row)
            req.state = "queued"
            req.replica = req.slot = req.prefix_row = -1
            req.prefix_len = 0
            req.generated.clear()          # KV died with the replica
            req.flying = 0
            req.first_token_at = None
            req.requeued += 1
            req.requeued_at = time.monotonic()
            self.requeued_total += 1
            _metrics.counter(
                "bluefog_requests_total",
                "serve request events by status (done/failed are terminal "
                "and count once; requeued counts once per eviction)"
            ).inc(status="requeued")
        self._active[replica].clear()
        # head requeue, original arrival order preserved among the evicted
        self._queue.extendleft(reversed(lost))
        _flight.record("serve", name=f"replica_{reason}", replica=replica,
                       requeued_requests=[r.id for r in lost])
        if not self.live_replicas():
            raise RuntimeError("every serving replica has failed")
        return lost

    def preempt_replica(self, replica: int, *, zone: Optional[int] = None,
                        grace: float = 0.0) -> List[Request]:
        """Evict a replica whose backing ranks were spot-preempted.

        Same mechanics as a chaos kill — the slice is reclaimed, so
        ``park=False``: in-flight requests requeue at the head and the
        prefix directory is rebuilt empty on a later
        :meth:`restore_replica` — but the flight event says *preempted*
        (with the zone and grace window) so postmortems blame the reclaim,
        not a crash.  When the capacity is re-granted, bring the replica
        back with :meth:`restore_replica`.
        """
        lost = self.fail_replica(replica, reason="preempted", park=False)
        _flight.record("serve", name="replica_preempt_notice",
                       replica=replica, zone=zone, grace=float(grace),
                       requeued=len(lost))
        return lost

    def restore_replica(self, replica: int) -> bool:
        """Bring a previously-failed replica back into rotation.
        Returns True if the replica was dead.

        A replica parked via ``fail_replica(park=True)`` re-admits
        traffic as-is — its slice never died, so its sealed prefix pages
        are still backed by live KV.  A replica that actually failed
        (chaos kill / health eviction) lost that KV with the slice, so
        its prefix directory is rebuilt empty here: re-attaching the old
        sealed rows would serve garbage KV to every later hit.
        """
        if replica not in self._dead:
            return False
        self._dead.discard(replica)
        parked = replica in self._parked
        self._parked.discard(replica)
        if not parked and self._prefix[replica] is not None:
            scfg = self.engine.scfg
            self._prefix[replica] = PrefixCache(
                scfg.prefix_pages, scfg.prefix_page_tokens,
                first_row=scfg.slots, replica=replica)
        _flight.record("serve", name="replica_restored", replica=replica,
                       parked=parked)
        return True

    # ------------------------------------------------------------------

    def _stage(self, name: str, **attrs) -> _tracing.stage:
        """``bf:serve.<name>`` in the profiler's trace (and the ring when
        armed): the host stages of one step, between the engine's calls."""
        return _tracing.stage(self._sched_trace, name, cat="serve", **attrs)

    def step(self) -> List[Request]:
        """One admit → decode → deliver cycle; returns requests retired
        this cycle.

        The decode runs ONE CALL AHEAD of the host: a request retires by
        length alone, so which lanes ride the next call and where they
        stand is known before the call in flight has been read, and the
        token such a lane feeds on goes from that call's output into the
        next call's input on the device.  So this step's call is packed
        and dispatched FIRST, then the call dispatched a step earlier is
        read back and its tokens delivered: a token reaches its request
        one call late, and the host's part of a step runs while the device
        is busy.  A prefill stays synchronous: it queues behind the call
        in flight, and its first token is appended before this step's call
        is packed.  With ``spec_decode`` a round's accepted counts are
        data, so nothing runs ahead: the round is read back and delivered
        in its own step.
        """
        with self._stage("step"):
            with self._stage("admit"):
                self._admit()
            retired = self._decode_once()
            _metrics.gauge("bluefog_serve_queue_depth",
                           "admission-queue depth after each scheduler step"
                           ).set(self.pending)
            if self._slo is not None:
                self._slo.observe(self)
        return retired

    def attach_slo(self, engine) -> None:
        """Attach an SLO engine (``diagnostics.SLOEngine``); its
        ``observe(sched)`` runs after every step."""
        self._slo = engine

    def drain(self, max_steps: int = 10_000) -> None:
        """Run until every submitted request reaches a terminal state
        (and no decode call is in flight)."""
        for _ in range(max_steps):
            if self.done:
                return
            self.step()
        raise RuntimeError(f"scheduler did not drain in {max_steps} steps")

    # ------------------------------------------------------------------

    def _prefill_request(self, req: Request, waited: float) -> int:
        """Prefill one admitted request — through a shared prefix page when
        one matches — and return its first token.  Observes the TTFT
        histogram with the hit/cold split."""
        with _tracing.stage(req.trace_id, "prefill", cat="serve",
                            prompt_len=len(req.prompt),
                            waited_us=int(waited * 1e6)) as st:
            first, hit = self._prefill_tokens(req)
            st.attrs.update(hit=hit, replica=req.replica,
                            prefix_len=req.prefix_len)
        return first

    def _prefill_tokens(self, req: Request):
        r, pc = req.replica, self._prefix[req.replica]
        hit = False
        if pc is not None:
            got = pc.acquire(req.prompt)
            if got is None:
                adm = pc.admit(req.prompt)
                if adm is not None:
                    # shareable miss: seal the prefix on the way in, then
                    # read through it ourselves — the "copy" of CoW is the
                    # divergent suffix landing in our private slot
                    row, plen = adm
                    self.engine.seal_prefix(r, row, req.prompt[:plen])
                    pc.seal(row)
                    pc.attach(row)
                    req.prefix_row, req.prefix_len = row, plen
            else:
                req.prefix_row, req.prefix_len = got
                hit = True
        if req.prefix_row >= 0:
            first = self.engine.chunk_prefill(
                r, req.slot, req.prompt[req.prefix_len:],
                req.prefix_len, req.prefix_row)
        else:
            first, _ = self.engine.prefill(r, req.slot, req.prompt)
        req.first_token_at = time.monotonic()
        _metrics.histogram(
            "bluefog_serve_ttft_hit_seconds" if hit
            else "bluefog_serve_ttft_cold_seconds",
            "time to first token, by prefix-cache outcome",
            buckets=LATENCY_BUCKETS).observe(
                req.first_token_at - req.submitted_at)
        return first, hit

    def _admit(self) -> None:
        # a lane needs a free KV slot AND a decode lane: never admit past
        # the largest declared batch bucket — undeclared lane counts have
        # no compiled program to run under
        lane_cap = min(self.engine.scfg.slots,
                       self.engine.scfg.batch_buckets[-1])
        while self._queue:
            candidates = [
                r for r in self.live_replicas()
                if (self._alloc[r].in_use < self.engine.scfg.slots
                    and len(self._active[r]) < lane_cap)]
            if not candidates:
                return                       # every live replica is full
            # prefix-affine routing: a replica already holding this
            # prompt's sealed prefix saves the whole shared prefill, which
            # beats perfect load balance; longest match wins, load breaks
            # ties.  Prefix caches are per-replica (the pages live in that
            # replica's cache rows), so without affinity a hot system
            # prompt would be re-sealed on every replica it strays to.
            head = self._queue[0]
            def _rank(r):
                pc = self._prefix[r]
                got = pc.match(head.prompt) if pc is not None else None
                # expert-load-aware tiebreak: among equally-loaded
                # replicas, prefer the one whose fused batch routes least
                # pathologically (quantized so transient jitter never
                # outranks a real load difference)
                return (-(got[1] if got else 0), len(self._active[r]),
                        self._expert_skew(r), r)
            target = min(candidates, key=_rank)
            req = self._queue.popleft()
            slot = self._alloc[target].alloc()
            req.replica, req.slot, req.state = target, slot, "running"
            t0 = time.monotonic()
            req.admitted_at = t0
            # a requeued request's second wait starts at eviction, not at
            # submit — starting at submitted_at would double-count the
            # first wait and let summed queue spans exceed the E2E total
            q0 = (req.requeued_at if req.requeued_at is not None
                  else req.submitted_at)
            _tracing.add_span(req.trace_id, "queue", q0, t0,
                              cat="serve", replica=target,
                              requeued=req.requeued)
            first = self._prefill_request(req, t0 - q0)
            req.generated.append(first)
            _metrics.counter(
                "bluefog_tokens_generated_total",
                "tokens produced by serve decode steps").inc()
            _metrics.histogram(
                "bluefog_serve_token_latency_seconds",
                "per-token serve latency (prefill + decode)",
                buckets=LATENCY_BUCKETS).observe(req.first_token_at - t0)
            self._active[target][slot] = req
            self._last_ids[target] = (self._last_ids[target] + [req.id])[-8:]
            self._maybe_retire(req)

    def _rides(self, req: Request) -> bool:
        """Whether ``req`` takes a lane of the next fused call: it appends
        at next_pos .. next_pos + window - 1, all of which must fit under
        the per-slot capacity (the window is a speculative round's k + 1
        when spec decode is armed), and tokens in flight count as
        generated."""
        return (len(req.generated) + req.flying < req.max_new_tokens
                and req.next_pos + self.engine.scfg.decode_window
                <= self.engine.scfg.max_len)

    def _decode_once(self) -> List[Request]:
        lanes = [[(slot, req) for slot, req in sorted(self._active[r].items())
                  if self._rides(req)] for r in range(self.replicas)]
        busiest = max((len(l) for l in lanes), default=0)
        if busiest == 0:
            # every live lane's last token is in flight, or none is live
            return self._settle()
        scfg = self.engine.scfg
        S = scfg.batch_bucket_for(busiest)
        R = self.replicas
        with self._stage("pack", lanes=busiest, S=S):
            idle_tok, idle_slot, idle_len = self.engine.idle_lane()
            toks = np.full((R, S), idle_tok, np.int32)
            slots = np.full((R, S), idle_slot, np.int32)
            lens = np.full((R, S), idle_len, np.int32)
            prows = np.full((R, S), idle_slot, np.int32)
            plens = np.zeros((R, S), np.int32)
            for r in range(R):
                for i, (slot, req) in enumerate(lanes[r]):
                    # the token the call in flight chooses for the slot
                    # stays on the device: the host has not read it yet
                    toks[r, i] = -1 if req.flying else req.generated[-1]
                    slots[r, i] = slot
                    lens[r, i] = req.next_pos
                    if req.prefix_row >= 0:
                        prows[r, i] = req.prefix_row
                        plens[r, i] = req.prefix_len
            pargs = (prows, plens) if self._prefix[0] is not None else (
                None, None)
        if scfg.spec_decode:
            # a round's accepted counts are data: where its lanes stand
            # next is unknown until it is read, so nothing runs ahead
            t0 = time.monotonic()
            emitted, counts = self.engine.spec_decode(toks, slots, lens,
                                                      *pargs)
            dt = time.monotonic() - t0
            gen_tokens = lambda r, i: \
                [int(t) for t in emitted[r, i, :counts[r, i]]]
            with self._stage("deliver"):
                return self._deliver(lanes, gen_tokens, counts,
                                     int(counts.max()), t0, dt)
        gen = self.engine.decode(toks, slots, lens, *pargs, ahead=True)
        return self._deliver_decode(gen, lanes)

    def _settle(self) -> List[Request]:
        """Read back and deliver the call in flight, if any, with nothing
        dispatched behind it."""
        if self._flying is None:
            return []
        return self._deliver_decode(self.engine.decode_drain(), None)

    def _deliver_decode(self, gen, lanes) -> List[Request]:
        """Deliver the call that was in flight, if any, whose tokens
        ``gen`` ``[R, steps, S]`` the engine just handed back, and note
        ``lanes`` (None: nothing was dispatched) as the call in flight
        now.  A call's clock runs from when the one ahead of it was read
        to when it is: its device time and whatever the host put in
        between."""
        now = time.monotonic()
        due, self._flying = self._flying, (
            None if lanes is None else (lanes, now))
        steps = self.engine.scfg.decode_steps_per_call
        for mine in lanes or ():
            for _, req in mine:
                req.flying += steps
        if due is None:
            return []
        lanes, t0 = due
        for mine in lanes:
            for _, req in mine:
                req.flying -= steps
        gen_tokens = lambda r, i: [int(t) for t in gen[r, :, i]]
        with self._stage("deliver"):
            return self._deliver(lanes, gen_tokens, None, gen.shape[1], t0,
                                 now - t0)

    def _deliver(self, lanes, gen_tokens, counts, steps, t0, dt
                 ) -> List[Request]:
        """Hand one fused call's tokens to their requests; retire the
        finished ones."""
        scfg = self.engine.scfg
        self._decode_calls += 1
        self._note_moe_load()
        traced = _tracing.enabled()
        n_tokens = 0
        retired: List[Request] = []
        for r in range(self.replicas):
            for i, (_, req) in enumerate(lanes[r]):
                room = req.max_new_tokens - len(req.generated)
                new = gen_tokens(r, i)[:room]
                req.generated.extend(new)
                n_tokens += len(new)
                if traced:
                    # one fused call covers every lane: each rider gets the
                    # same [t0, t0+dt) span, tagged with ITS token yield
                    if scfg.spec_decode:
                        _tracing.add_span(
                            req.trace_id, "decode", t0, t0 + dt, cat="serve",
                            call=self._decode_calls, tokens=len(new),
                            accepted=int(counts[r, i]),
                            rejected=int(scfg.spec_decode - counts[r, i] + 1))
                    else:
                        _tracing.add_span(
                            req.trace_id, "decode", t0, t0 + dt, cat="serve",
                            call=self._decode_calls, tokens=len(new))
                done = self._maybe_retire(req)
                if done:
                    retired.append(req)
        if n_tokens:
            _metrics.counter(
                "bluefog_tokens_generated_total",
                "tokens produced by serve decode steps").inc(n_tokens)
            h = _metrics.histogram(
                "bluefog_serve_token_latency_seconds",
                "per-token serve latency (prefill + decode)",
                buckets=LATENCY_BUCKETS)
            for _ in range(min(steps, 64)):   # bounded observer cost
                h.observe(dt / max(steps, 1))
        return retired

    def _note_moe_load(self) -> None:
        """Snapshot the engine's per-replica routing load (None for dense
        engines) and publish the hot-expert gauges the fleet watches:
        the hottest expert's top-1 dispatch fraction, the mean router
        entropy, and the full per-(replica, expert) fraction surface."""
        self._moe_load = load = self.engine.moe_load()
        if load is None:
            return
        hot = _metrics.gauge(
            "bluefog_serve_hot_expert_fraction",
            "top-1 dispatch fraction of the hottest expert in the last "
            "fused MoE batch, per replica")
        ent = _metrics.gauge(
            "bluefog_serve_router_entropy",
            "mean live-token router entropy (nats) of the last fused MoE "
            "batch, per replica")
        per = _metrics.gauge(
            "bluefog_serve_expert_load_fraction",
            "top-1 dispatch fraction per (replica, expert) in the last "
            "fused MoE batch")
        for r, row in enumerate(load):
            if not row["tokens"]:
                continue
            hot.set(float(row["fractions"].max()), replica=r)
            ent.set(row["entropy"], replica=r)
            for e, f in enumerate(row["fractions"]):
                per.set(float(f), replica=r, expert=e)

    def _expert_skew(self, r: int) -> int:
        """Quantized routing skew of replica ``r``'s last fused batch: the
        hottest expert's excess dispatch fraction over perfect balance, in
        eighths (0 for dense engines, balanced batches, or no data yet).
        Admission uses this as a tiebreak so a replica whose batch already
        hammers one expert peer stops attracting more load than its
        balanced siblings."""
        load = self._moe_load
        if not load or r >= len(load) or not load[r]["tokens"]:
            return 0
        frac = load[r]["fractions"]
        return int((float(frac.max()) - 1.0 / len(frac)) * 8)

    def _maybe_retire(self, req: Request) -> bool:
        if req.flying or self._rides(req):
            return False
        req.state = "done"
        req.finished_at = time.monotonic()
        self._active[req.replica].pop(req.slot, None)
        self._alloc[req.replica].free(req.slot)
        if req.prefix_row >= 0:
            self._prefix[req.replica].release(req.prefix_row)
        # root span: its [submitted_at, finished_at) duration IS the
        # request's measured E2E latency — trace_report checks children
        # against it
        _tracing.add_span(req.trace_id, "request", req.submitted_at,
                          req.finished_at, cat="serve",
                          tokens=len(req.generated), replica=req.replica,
                          requeued=req.requeued)
        self.completed.append(req)
        _metrics.counter(
            "bluefog_requests_total",
            "serve request events by status (done/failed are terminal "
            "and count once; requeued counts once per eviction)"
        ).inc(status="done")
        return True

    # ------------------------------------------------------------------

    def _flight_block(self) -> dict:
        """The ``serve`` bundle block postmortem reads after a chaos kill."""
        now = time.monotonic()
        block = {
            "replicas": self.replicas,
            "dead_replicas": sorted(self._dead),
            "parked_replicas": sorted(self._parked),
            "pending": self.pending,
            "in_flight": {str(r): sorted(req.id
                                         for req in self._active[r].values())
                          for r in range(self.replicas) if self._active[r]},
            # per-request detail at dump time: trace ids + ages, so a
            # postmortem names the requests a dead replica took down
            "in_flight_traces": {
                str(r): [{"id": req.id, "trace": req.trace_id,
                          "age_s": round(now - req.submitted_at, 6),
                          "queue_s": round(
                              (req.admitted_at if req.admitted_at is not None
                               else now) - req.submitted_at, 6)}
                         for _, req in sorted(self._active[r].items())]
                for r in range(self.replicas) if self._active[r]},
            "queued": [{"id": q.id, "trace": q.trace_id,
                        "age_s": round(now - q.submitted_at, 6)}
                       for q in list(self._queue)[:16]],
            "last_request_ids": {str(r): ids for r, ids
                                 in enumerate(self._last_ids) if ids},
            "completed": len(self.completed),
            "failed": [r.id for r in self.failed],
            "requeued": self.requeued_total,
        }
        if self._prefix[0] is not None:
            block["prefix_pages"] = {
                str(r): self._prefix[r].describe()
                for r in self.live_replicas() if self._prefix[r].in_use}
        if self._moe_load is not None:
            block["moe"] = {
                str(r): {
                    "fractions": [round(float(f), 6)
                                  for f in row["fractions"]],
                    "entropy": round(row["entropy"], 6),
                    "tokens": row["tokens"],
                    "skew_eighths": self._expert_skew(r),
                }
                for r, row in enumerate(self._moe_load) if row["tokens"]}
        return block

    def close(self) -> None:
        """Stop scheduling: the call in flight, if any, is read back and
        delivered, so the engine is left with nothing in flight."""
        self._settle()
        _flight.unregister_block("serve")


class AutoScaler:
    """SLO-driven serve autoscaling: breaches write the scale file.

    Watches two signals after every :meth:`Scheduler.step` — the
    admission-queue depth and a trailing-window p99 of
    ``bluefog_serve_token_latency_seconds`` read from the time-series
    store (:mod:`bluefog_tpu.utils.timeseries`; the scaler arms the
    ring itself, and falls back to an EWMA over the histogram's
    reservoir percentile for observations that predate arming) — and
    closes the
    elastic loop: a sustained breach *grows* the serving fleet (restores
    the lowest PARKED replica — one retired by this scaler, whose slice
    is intact; a chaos-killed replica's KV died with it and is never
    re-admitted here — AND writes the new target into the bfrun scale
    file so the supervisor regrows the world under it), a quiet queue
    well under the SLO *retires* the highest live replica after a
    cooldown.  Retirement uses the requeue path, so shrinking never
    fails a request.

    The scale file speaks the supervisor's unit: RANKS (world size), not
    replicas.  Each serve replica is a PP×TP×SP slice of
    ``ranks_per_replica`` ranks (default: the engine mesh's
    ``slice_size``), so every action writes
    ``live_replicas * ranks_per_replica``.

    Knobs (env defaults): ``BLUEFOG_AUTOSCALE`` gates
    :meth:`enabled_from_env`; ``BLUEFOG_SLO_P99_MS`` sets the p99 target
    (default 250 ms).  ``cooldown_steps`` applies between any two scale
    actions in either direction.
    """

    def __init__(self, sched: Scheduler, *,
                 slo_p99_s: Optional[float] = None,
                 queue_high: Optional[int] = None,
                 cooldown_steps: int = 50,
                 scale_file: Optional[str] = None,
                 min_replicas: int = 1,
                 alpha: float = 0.2,
                 window_s: float = 60.0,
                 ranks_per_replica: Optional[int] = None):
        from ..utils.config import env_float
        if slo_p99_s is None:
            slo_p99_s = env_float("BLUEFOG_SLO_P99_MS", 250.0) / 1000.0
        if slo_p99_s <= 0:
            raise ValueError(f"slo_p99_s must be > 0, got {slo_p99_s}")
        if queue_high is None:
            # headroom of one full refill of every live replica's slots
            queue_high = 2 * sched.engine.scfg.slots * max(
                1, len(sched.live_replicas()))
        self.sched = sched
        self.slo_p99_s = float(slo_p99_s)
        self.queue_high = int(queue_high)
        self.cooldown_steps = int(cooldown_steps)
        self.scale_file = scale_file
        self.min_replicas = max(1, int(min_replicas))
        if ranks_per_replica is None:
            # replicas -> ranks: each serve replica is one PP*TP*SP slice
            ranks_per_replica = getattr(
                getattr(sched.engine, "m", None), "slice_size", 1)
        if int(ranks_per_replica) < 1:
            raise ValueError(
                f"ranks_per_replica must be >= 1, got {ranks_per_replica}")
        self.ranks_per_replica = int(ranks_per_replica)
        self.alpha = float(alpha)
        if window_s <= 0:
            raise ValueError(f"window_s must be > 0, got {window_s}")
        self.window_s = float(window_s)
        # every future latency observation also lands in a bounded ring;
        # observe() scores the trailing window instead of the lifetime
        # reservoir
        _ts.arm("bluefog_serve_token_latency_seconds")
        self.ewma_p99: Optional[float] = None
        self.events: List[dict] = []
        self._step = 0
        self._last_action_step = -cooldown_steps

    @staticmethod
    def enabled_from_env() -> bool:
        from ..utils.config import env_flag
        return env_flag("BLUEFOG_AUTOSCALE", False)

    # ------------------------------------------------------------------

    def _write_scale(self, target: int) -> None:
        if self.scale_file is None:
            return
        from ..run.launcher import _write_scale
        _write_scale(self.scale_file, target)

    def _record(self, action: str, replica: int) -> None:
        live = len(self.sched.live_replicas())
        target_world = live * self.ranks_per_replica
        ev = {"step": self._step, "action": action, "replica": replica,
              "live_replicas": live,
              "target_world": target_world,
              "pending": self.sched.pending,
              "ewma_p99_s": self.ewma_p99}
        self.events.append(ev)
        self._last_action_step = self._step
        # the supervisor's unit is ranks, not replicas
        self._write_scale(target_world)
        _metrics.counter(
            "bluefog_autoscale_events_total",
            "autoscale actions by direction").inc(action=action)
        _flight.record("autoscale", name=action, replica=replica,
                       live_replicas=live, target_world=target_world,
                       pending=self.sched.pending,
                       ewma_p99_s=self.ewma_p99)

    # ------------------------------------------------------------------

    def observe(self) -> Optional[dict]:
        """Fold in one scheduler step; returns the scale event if one
        fired.  Call once per :meth:`Scheduler.step`."""
        self._step += 1
        # primary: exact p99 over the trailing window of the armed ring
        p99 = _ts.percentile("bluefog_serve_token_latency_seconds", 99,
                             window_s=self.window_s)
        if p99 is None:
            # ring empty (observations predate arming): EWMA over the
            # lifetime reservoir percentile, the pre-timeseries behavior
            raw = _metrics.histogram(
                "bluefog_serve_token_latency_seconds",
                "per-token serve latency (prefill + decode)",
                buckets=LATENCY_BUCKETS).percentile(99)
            if raw is not None:
                p99 = (raw if self.ewma_p99 is None else
                       self.alpha * raw + (1.0 - self.alpha) * self.ewma_p99)
        if p99 is not None:
            self.ewma_p99 = p99
            _ts.append("bluefog_serve_p99_s", p99)
            # also a registry gauge: the fleet-view carrier gossips it,
            # so every rank's scaler scores the fleet's worst p99
            _metrics.gauge(
                "bluefog_serve_p99_s",
                "trailing-window p99 of per-token serve latency (s)"
                ).set(p99)
        if self._step - self._last_action_step < self.cooldown_steps:
            return None
        sched = self.sched
        # fleet re-basing: with a gossiped fleet view armed, a queue or
        # p99 breach anywhere in the fleet is scored here too — the rank
        # holding the parked replica acts even when its local signals are
        # calm (the retire path stays strictly local)
        fleet_pending = fleet_p99 = None
        fv = _fleetview.active()
        if fv is not None:
            fleet_pending, _ = fv.fleet_max("bluefog_serve_queue_depth")
            fleet_p99, _ = fv.fleet_max("bluefog_serve_p99_s")
        eff_pending = max(sched.pending, int(fleet_pending or 0))
        eff_p99 = max((x for x in (self.ewma_p99, fleet_p99)
                       if x is not None), default=None)
        breach = (eff_pending > self.queue_high
                  or (eff_p99 is not None and eff_p99 > self.slo_p99_s))
        if breach and sched._parked:
            # only autoscale-parked replicas re-admit traffic: a
            # chaos-killed/health-evicted one lost its KV with the slice
            replica = min(sched._parked)
            sched.restore_replica(replica)
            self._record("grow", replica)
            return self.events[-1]
        live = sched.live_replicas()
        calm = (not breach and sched.pending == 0
                and (self.ewma_p99 is None
                     or self.ewma_p99 < 0.5 * self.slo_p99_s))
        if calm and len(live) > self.min_replicas:
            replica = max(live)
            sched.fail_replica(replica, reason="retired", park=True)
            self._record("retire", replica)
            return self.events[-1]
        return None
