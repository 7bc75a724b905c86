"""The scheduler runs one decode call ahead of the host.

What is pinned here:

* **the same tokens** — every request through the run-ahead ``Scheduler``
  (call n+1 dispatched before call n is read back, each lane fed on the
  device with the token call n chooses for its slot) gets the tokens of a
  loop written out here over the synchronous ``engine.prefill`` /
  ``engine.decode(tokens, ...)``; a control feeds the lanes a stale token
  (the call before the one in flight) and the streams drift; with
  ``spec_decode`` armed nothing runs ahead;
* **the order and the crossings** — the stages of a decode call keep their
  names under one ``decode_call``, the ``collect`` behind a ``dispatch``
  waits for the call ahead of it, one array in and one out a call;
* **the edges with a call in flight** — a replica failed or preempted and
  restored between steps, ``drain`` and ``close``, a request of one token
  and one that ends at ``max_len``, and whose logits ``decode_logits``
  hands out after a step.
"""
import numpy as np
import pytest

from bluefog_tpu.parallel import compose
from bluefog_tpu.serve import Scheduler, ServeConfig, ServeEngine
from bluefog_tpu.utils import flight as bfflight
from bluefog_tpu.utils import metrics as bfm
from bluefog_tpu.utils import tracing as bftrace
from test_serve_hybrid import make_engine as make_hybrid_engine

_CFG = dict(vocab=32, d_model=32, heads=4, layers=2, seq_len=32)
_SCFG = dict(batch_buckets=(2, 4), prefill_buckets=(4, 8), slots=4,
             max_len=24, seed=11)


@pytest.fixture(autouse=True)
def _clean():
    bfm.reset_metrics()
    bftrace.reset()
    bfflight.reset()
    yield
    bftrace.reset()
    bfflight.reset()
    bfm.reset_metrics()


def _engine(cpu_devices, dp=1, **scfg):
    cfg = compose.LMConfig(**_CFG)
    m = compose.compose_parallelism(dp, 1, 1, 1, devices=cpu_devices[:dp])
    eng = ServeEngine(m, cfg, compose.init_lm_params(cfg, m, seed=3),
                      ServeConfig(**{**_SCFG, **scfg}))
    eng.warmup()
    return eng


def _calls(ahead):
    return bfm.counter("bluefog_serve_decode_calls_total").value(
        ahead=str(ahead))


def _alone(eng, req, count=None):
    """``req``'s tokens from the synchronous calls, the request alone in
    the slot the scheduler gave it: ``prefill`` (through its sealed page,
    ``chunk_prefill``), then ``decode`` with the last token handed over by
    the host until the room the scheduler's rule leaves is used up.
    ``count`` replays the admission it was (the sampler's key schedule)."""
    scfg, dp = eng.scfg, eng.m.dp
    if count is not None:
        eng._seed_count = count - 1
    if req.prefix_row >= 0:
        eng.seal_prefix(req.replica, req.prefix_row,
                        req.prompt[:req.prefix_len])
        out = [eng.chunk_prefill(req.replica, req.slot,
                                 req.prompt[req.prefix_len:], req.prefix_len,
                                 req.prefix_row)]
    else:
        out = [eng.prefill(req.replica, req.slot, req.prompt)[0]]
    S = scfg.batch_buckets[0]
    idle_t, idle_s, idle_l = eng.idle_lane()
    while (len(out) < req.max_new_tokens and len(req.prompt) + len(out) - 1
           + scfg.decode_window <= scfg.max_len):
        toks = np.full((dp, S), idle_t, np.int32)
        slots = np.full((dp, S), idle_s, np.int32)
        lens = np.full((dp, S), idle_l, np.int32)
        prows, plens = slots.copy(), lens.copy()
        at = req.replica, 0
        toks[at], slots[at] = out[-1], req.slot
        lens[at] = len(req.prompt) + len(out) - 1
        if req.prefix_row >= 0:
            prows[at], plens[at] = req.prefix_row, req.prefix_len
        gen = eng.decode(toks, slots, lens,
                         *((prows, plens) if scfg.prefix_pages else ()))
        out += [int(t) for t in gen[req.replica, :, 0]]
    return out[:req.max_new_tokens]


def _prompts(rng, shared=None):
    lens, news = (3, 5, 8, 4, 6, 7, 2), (6, 1, 9, 3, 2, 7, 5)
    prompts = [rng.integers(0, _CFG["vocab"], n).tolist() for n in lens]
    if shared:
        prompts[2] = shared + prompts[2][:3]
        prompts[5] = shared + prompts[5][:2]
    return prompts, news


@pytest.mark.parametrize(
    "temperature,dp,steps,prefix,control",
    [(t, dp, steps, prefix, False) for t in (0.0, 0.8) for dp in (1, 2)
     for steps in (1, 2) for prefix in (False, True)]
    + [(0.0, 1, 1, False, True), (0.8, 1, 1, False, True)])
def test_run_ahead_scheduler_serves_the_synchronous_loops_tokens(
        cpu_devices, temperature, dp, steps, prefix, control):
    """Seven requests over four slots (slots reused, one request of a
    single token, with ``prefix`` two that share a sealed page): each one's
    tokens equal the synchronous loop's.  ``control``: the lanes are fed
    what the call BEFORE the one in flight chose, a token one call stale;
    the streams drift from the loop's."""
    eng = _engine(cpu_devices, dp, temperature=temperature,
                  decode_steps_per_call=steps,
                  **(dict(prefix_pages=2, prefix_page_tokens=4)
                     if prefix else {}))
    prompts, news = _prompts(np.random.default_rng(7),
                             [3, 1, 4, 1] if prefix else None)
    base, warm = eng._seed_count, _calls(0)
    sched = Scheduler(eng)
    reqs = [sched.submit(p, max_new_tokens=n) for p, n in zip(prompts, news)]
    if control:
        feed, fed = eng._feed_jit, []

        def stale(lanes, gen):
            fed.append(gen)
            old = fed[-2] if len(fed) > 1 else gen
            return feed(lanes, old if old.shape == gen.shape else gen)
        stale._cache_size = feed._cache_size
        eng._feed_jit = stale
    for step in range(1, 200):
        if sched.done:
            break
        sched.step()
    assert sched.done and eng._flying is None
    sched.close()
    assert _calls(1) > _calls(0) - warm > 0
    admitted = sorted(reqs, key=lambda r: r.admitted_at)
    same = []
    for count, req in enumerate(admitted, base + 1):
        want = _alone(eng, req, count)
        assert len(req.generated) == len(want) == req.max_new_tokens
        assert req.generated[:2] == want[:2]    # chosen before any feed
        same.append(req.generated == want)
    assert same.count(False) >= 2 if control else all(same), same
    if prefix:
        assert bfm.counter("bluefog_serve_prefix_hits_total").total() >= 1
    assert bfm.counter("bluefog_retrace_after_warmup_total").total() == 0


def test_speculation_never_runs_ahead(cpu_devices):
    """A round's accepted counts are data: with ``spec_decode`` armed every
    call of the scheduler is dispatched with nothing in flight, and its
    tokens are delivered in its own step."""
    cfg = compose.LMConfig(**{**_CFG, "layers": 4})
    m = compose.compose_parallelism(1, 2, 1, 1, devices=cpu_devices[:2])
    eng = ServeEngine(m, cfg, compose.init_lm_params(cfg, m, seed=3),
                      ServeConfig(spec_decode=2, spec_stages=1, **_SCFG))
    eng.warmup()
    warm = _calls(0)
    sched = Scheduler(eng)
    prompts, news = _prompts(np.random.default_rng(7))
    reqs = [sched.submit(p, max_new_tokens=n) for p, n in zip(prompts, news)]
    sched.step()
    assert max(len(r.generated) for r in reqs) > 1      # its own step's
    sched.drain()
    sched.close()
    assert _calls(0) > warm and _calls(1) == 0
    assert [len(r.generated) for r in reqs] == list(news)


def test_a_decode_call_is_dispatched_before_the_one_ahead_is_read(
        cpu_devices, tmp_path):
    """The ring armed over one drain: every ``decode_call`` holds exactly
    one ``stage_in``, ``dispatch`` and ``collect`` in that order; the first
    finds nothing in flight (``ahead=0``) and its ``collect`` reads
    nothing, every later one is dispatched while its predecessor is still
    unread (``ahead=1``) and its ``collect`` reads THAT one, so a step
    delivers the call before its own; what the last call chose is read by
    one ``decode_drain``; one array in and one out a call."""
    eng = _engine(cpu_devices)
    moved = bfm.counter("bluefog_serve_host_arrays_total")
    crossed = lambda d: moved.value(program="decode", direction=d)
    before = crossed("in"), crossed("out"), _calls(0), _calls(1)
    bftrace.configure(str(tmp_path))
    sched = Scheduler(eng)
    prompts, news = _prompts(np.random.default_rng(5))
    reqs = [sched.submit(p, max_new_tokens=n) for p, n in zip(prompts, news)]
    seen = []
    while not sched.done:
        sched.step()
        seen.append([len(r.generated) for r in reqs])
    sched.close()
    spans = [s for s in bftrace.spans() if s.get("cat") == "engine"]
    bftrace.configure(None)
    within = lambda name, o: [s for s in spans if s["name"] == name
                              and o["t0"] <= s["t0"] and s["t1"] <= o["t1"]]
    calls = sorted((s for s in spans if s["name"] == "decode_call"),
                   key=lambda s: s["t0"])
    assert len(calls) >= 4
    for call in calls:
        (i,), (d,), (c,) = (within(n, call)
                            for n in ("stage_in", "dispatch", "collect"))
        assert i["t1"] <= d["t0"] and d["t1"] <= c["t0"]
    assert [c["ahead"] for c in calls] == [0] + [1] * (len(calls) - 1)
    drain, = (s for s in spans if s["name"] == "decode_drain")
    assert len(within("collect", drain)) == 1 and drain["t0"] >= calls[-1]["t1"]
    # the step of the first call delivers the prefills' tokens alone (the
    # second request's one token frees its slot for the fifth), the next
    # step that call's
    assert seen[0] == [1, 1, 1, 1, 1, 0, 0], seen[:3]
    assert seen[1] == [2, 1, 2, 2, 2, 0, 0], seen[:3]
    n = len(calls)
    assert (crossed("in") - before[0], crossed("out") - before[1]) == (n, n)
    assert (_calls(0) - before[2], _calls(1) - before[3]) == (1, n - 1)


@pytest.mark.parametrize("edge", ["fail_restore", "preempt_restore", "drain",
                                  "close", "one_token", "max_len",
                                  "hybrid_logits"])
def test_edges_with_a_call_in_flight(cpu_devices, edge):
    if edge == "hybrid_logits":
        # after each step the logits handed out are those of the call whose
        # tokens that step appended (the benchmark's check pairs them so)
        eng = make_hybrid_engine(cpu_devices, decode_steps_per_call=2)
        eng.warmup()
        sched = Scheduler(eng)
        rng = np.random.default_rng(3)
        reqs = [sched.submit(rng.integers(0, 128, n).tolist(),
                             max_new_tokens=k)
                for n, k in ((5, 6), (9, 3), (3, 7))]
        paired = 0
        while not sched.done:
            had = [len(r.generated) for r in reqs]
            sched.step()
            slots, rows = eng.decode_logits(0)
            lane = {int(s): i for i, s in enumerate(slots)}
            rows = np.asarray(rows)                     # [steps, S, vocab]
            for r, n0 in zip(reqs, had):
                first = max(n0, 1)                      # [0] is the prefill's
                for j in range(first, len(r.generated)):
                    assert r.generated[j] == int(
                        rows[j - first, lane[r.slot]].argmax())
                    paired += 1
        sched.close()
        assert paired == sum(len(r.generated) - 1 for r in reqs) == 13
        return
    dp = 2 if edge.endswith("restore") else 1
    eng = _engine(cpu_devices, dp)
    sched = Scheduler(eng)
    rng = np.random.default_rng(9)
    new = {"one_token": (1, 1, 6, 1), "max_len": (40, 3, 40)}.get(
        edge, (7, 5, 9, 6, 4))
    reqs = [sched.submit(rng.integers(0, _CFG["vocab"], 3 + i).tolist(),
                         max_new_tokens=n) for i, n in enumerate(new)]
    for _ in range(3):
        sched.step()
    if edge.endswith("restore"):
        assert sched._flying is not None and eng._flying is not None
        hit = [r for r in reqs if r.replica == 1 and r.state == "running"]
        assert hit and any(r.flying for r in hit)
        lost = sched.fail_replica(1) if edge == "fail_restore" \
            else sched.preempt_replica(1, zone=3, grace=0.5)
        assert {r.id for r in lost} == {r.id for r in hit} and all(
            r.generated == [] and r.flying == 0 for r in lost)
        sched.restore_replica(1)
    if edge == "close":
        assert sched._flying is not None
        sched.close()
        held = [len(r.generated) for r in reqs]
        assert eng._flying is None and sched._flying is None
        assert not any(r.flying for r in reqs)
        for leaf in eng.cache.values():
            leaf.delete()                   # nothing queued still wants it
        assert held == [4, 4, 4, 4, 0]
        return
    sched.drain()
    assert sched.done and eng._flying is None and sched._flying is None
    sched.close()
    for req in reqs:
        assert req.state == "done" and req.generated == _alone(eng, req), \
            req.id
    if edge == "max_len":
        # the slot's room decides: the last position a call may write is
        # max_len - 1, whatever was asked
        assert [len(r.generated) for r in reqs] == [
            _SCFG["max_len"] - 3 + 1, 3, _SCFG["max_len"] - 5 + 1]
    else:
        assert [len(r.generated) for r in reqs] == list(new)
    if edge.endswith("restore"):
        assert sched.requeued_total == len(lost)
    assert bfm.counter("bluefog_retrace_after_warmup_total").total() == 0
