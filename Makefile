# Test / build matrix (counterpart of the reference's mpirun-driven Makefile,
# Makefile:22-62 — here the "cluster" is the 8-device virtual CPU mesh the
# conftest provisions, so plain pytest plays the role of `mpirun -np 4 pytest`).

PY ?= python

.PHONY: test test-fast test_basic test_ops test_win_ops test_optimizer \
	test_hier test_native test_examples verify native clean \
	obs-smoke obs-trace-smoke chaos-smoke overlap-smoke postmortem-smoke \
	pod-smoke autotune-smoke elastic-smoke async-smoke preempt-smoke \
	fleet-smoke

test:
	$(PY) -m pytest tests/ -q

# the CI tier: skips tests marked `slow` (multi-process bootstraps and
# compile-heavy end-to-end sweeps) so the whole run fits a short budget
test-fast:
	$(PY) -m pytest tests/ -q -m "not slow"

# everything verifiable without hardware: suite + example smokes + the
# multi-chip dryrun the driver runs
verify: test test_examples
	$(PY) -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

test_basic:
	$(PY) -m pytest tests/test_topology.py tests/test_schedule.py -q

test_ops:
	$(PY) -m pytest tests/test_ops.py tests/test_ring.py tests/test_fusion.py -q

test_win_ops:
	$(PY) -m pytest tests/test_win_ops.py -q

test_optimizer:
	$(PY) -m pytest tests/test_optimizers.py tests/test_haiku.py -q

test_hier:
	$(PY) -m pytest tests/test_hierarchical.py -q

test_native:
	$(PY) -m pytest tests/test_native.py -q

# e2e example smoke (counterpart of test/test_all_example.sh)
test_examples:
	$(PY) examples/average_consensus.py --virtual-cpu --data-size 100
	$(PY) examples/average_consensus.py --virtual-cpu --dynamic
	$(PY) examples/decentralized_optimization.py --virtual-cpu
	$(PY) examples/benchmark.py --virtual-cpu --model mlp --num-iters 3
	$(PY) examples/benchmark.py --virtual-cpu --model mlp --num-iters 3 \
		--dist-optimizer allreduce
	$(PY) examples/benchmark.py --virtual-cpu --model mlp --num-iters 3 \
		--dist-optimizer zero_allreduce
	$(PY) examples/benchmark.py --virtual-cpu --model mlp --num-iters 3 \
		--dist-optimizer choco
	$(PY) examples/mnist.py --virtual-cpu --epochs 1
	$(PY) examples/mnist.py --virtual-cpu --epochs 1 --dynamic-topology --atc
	$(PY) examples/resnet.py --virtual-cpu --epochs 1 --warmup-epochs 0 \
		--train-size 256 --batch-size 8
	$(PY) examples/haiku_mnist.py --virtual-cpu --epochs 1
	$(PY) examples/torch_migration.py --virtual-cpu --epochs 1
	$(PY) examples/long_context.py --virtual-cpu --steps 10
	$(PY) examples/long_context.py --virtual-cpu --steps 10 \
		--sp-layout zigzag --rope
	$(PY) examples/moe.py --virtual-cpu --steps 20
	$(PY) examples/moe.py --virtual-cpu --steps 30 --top2
	$(PY) examples/moe_lm.py --virtual-cpu --steps 40
	$(PY) examples/pipeline_lm.py --virtual-cpu --steps 30
	$(PY) examples/pipeline_lm.py --virtual-cpu --steps 30 --interleaved 2 \
		--micro 4
	$(PY) examples/pipeline_lm.py --virtual-cpu --steps 30 --hetero
	$(PY) examples/llm_3d.py --virtual-cpu --steps 40
	$(PY) examples/elastic_restart.py --virtual-cpu --steps 60

# observability smoke: both post-processing tools against the committed
# fixtures, then a schema check on their output JSON — exporter format
# drift fails here (and in tier-1, via the same fixtures in
# tests/test_trace_tools.py / tests/test_metrics.py)
obs-smoke:
	$(PY) tools/trace_analyze.py tests/fixtures/obs_trace.trace.json \
		--out /tmp/obs_trace_split.json
	$(PY) tools/metrics_report.py \
		tests/fixtures/metrics_host0.metrics.jsonl \
		tests/fixtures/metrics_host1.metrics.jsonl \
		--out /tmp/obs_metrics_report.json
	$(PY) -c "import json; \
		d = json.load(open('/tmp/obs_trace_split.json')); \
		assert d['ok'] and all(k in d for k in ('wall_ms', 'compute_ms', \
		'comm_ms', 'comm_exposed_ms', 'overlap_fraction', 'idle_ms')), d; \
		r = json.load(open('/tmp/obs_metrics_report.json')); \
		assert r['ok'] and r['n_hosts'] == 2 and all(k in r for k in \
		('metrics', 'series', 'summary')), r; \
		print('obs-smoke OK')"

# request-tracing smoke: the span/timeseries/SLO pytest battery (including
# the traced 8-rank estate drill and the flash-crowd burn-rate acceptance)
# plus trace_report over the committed two-rank bundles with a schema +
# critical-path check — bundle/report format drift fails here (and in
# tier-1, via the same fixtures in tests/test_tracing.py)
obs-trace-smoke:
	$(PY) -m pytest tests/test_tracing.py -q
	$(PY) tools/trace_report.py \
		tests/fixtures/trace_rank0.trace.jsonl \
		tests/fixtures/trace_rank1.trace.jsonl \
		--out /tmp/obs_trace_report.json \
		--chrome /tmp/obs_chrome_trace.json
	$(PY) -c "import json; \
		d = json.load(open('/tmp/obs_trace_report.json')); \
		assert d['ok'] and d['schema'] == 'bluefog-trace-report-1', d; \
		assert d['n_ranks'] == 2 and d['ranks'] == [0, 1], d; \
		r = d['requests']['req-r0-1']; \
		assert abs(r['queue_s'] + r['prefill_s'] + r['decode_s'] \
		+ r['gap_s'] - r['total_s']) < 1e-9, r; \
		assert d['critical_path'][0][0] == 'req-r0-1', d; \
		assert d['train']['steps'] == 2, d; \
		c = json.load(open('/tmp/obs_chrome_trace.json')); \
		assert c['traceEvents'] and any(e['ph'] == 'X' \
		for e in c['traceEvents']), c; \
		print('obs-trace-smoke OK')"

# pipelined-gossip smoke: the CPU-feasible overlap battery (delayed-CTA
# trajectory/HLO/contract tests, round-parallel equivalence) plus a schema
# check of trace_analyze's per-op exposed-time attribution on the committed
# overlapped-step fixture — the same tests run in tier-1 (none are `slow`)
overlap-smoke:
	$(PY) -m pytest tests/test_overlap.py -q
	$(PY) tools/trace_analyze.py tests/fixtures/overlap_trace.trace.json \
		--out /tmp/overlap_trace_split.json
	$(PY) -c "import json; \
		d = json.load(open('/tmp/overlap_trace_split.json')); \
		assert d['ok'] and all(k in d for k in ('comm_exposed_ms', \
		'overlap_fraction', 'top_exposed_comm_ops')), d; \
		rows = d['top_exposed_comm_ops']; \
		assert rows and all(set(r) == {'name', 'count', 'total_ms', \
		'exposed_ms'} for r in rows), rows; \
		print('overlap-smoke OK')"

# postmortem smoke: merge the committed two-rank flight bundles (rank 1
# chaos-killed at step 30, rank 0 SIGTERM'd by the teardown) and check the
# verdict schema — bundle/report format drift fails here (and in tier-1,
# via the same fixtures in tests/test_flight.py)
postmortem-smoke:
	$(PY) tools/postmortem.py \
		tests/fixtures/flight_rank0.json \
		tests/fixtures/flight_rank1.json \
		--out /tmp/postmortem_report.json
	$(PY) -c "import json; \
		d = json.load(open('/tmp/postmortem_report.json')); \
		assert d['ok'] and d['schema'] == 'bluefog-flight-1', d; \
		assert all(k in d for k in ('verdict', 'per_rank', 'step_time', \
		'consensus', 'topology')), d; \
		v = d['verdict']; \
		assert v['first_failed_rank'] == 1 and v['failure_step'] == 30, v; \
		print('postmortem-smoke OK')"

# elastic smoke: the membership battery (admit/retire/warmup/bootstrap,
# the interleaving invariant sweep, the kill-2-join-3 acceptance run) plus
# a postmortem over mixed-rank-count bundles — ranks born mid-run dump a
# grown world view; the report must note the split and keep its schema
elastic-smoke:
	$(PY) -m pytest tests/test_membership.py -q
	$(PY) tools/postmortem.py \
		tests/fixtures/flight_elastic_rank0.json \
		tests/fixtures/flight_elastic_rank8.json \
		--out /tmp/postmortem_elastic.json
	$(PY) -c "import json; \
		d = json.load(open('/tmp/postmortem_elastic.json')); \
		assert d['ok'] and d['schema'] == 'bluefog-flight-1', d; \
		assert all(k in d for k in ('verdict', 'per_rank', 'step_time', \
		'consensus', 'topology')), d; \
		t = d['topology']; \
		assert t['size'] == 11 and t['sizes_seen'] == [8, 11], t; \
		assert any('rank counts differ' in n for n in d['notes']), d; \
		print('elastic-smoke OK')"

# preemptible-fleet smoke: the preempt pytest battery (chaos preempt kind,
# trace grammar, launcher drain, warm executable pool, staleness
# controller, repeated-abort atomicity) plus the mass-preemption goodput
# drill — trace generated fresh, replayed through preempt_bench with its
# three gates (goodput floor, float64 continuity, zero-fresh-compile warm
# regrowth), and the flight bundle must yield a "preempted" blame
preempt-smoke:
	$(PY) -m pytest tests/test_preempt.py -q -m "not slow"
	rm -rf /tmp/preempt_flight
	$(PY) tools/preempt_trace.py --pattern mass --world 4 --zones 2 \
		--duration 8 --grace 1 --regrant 3 \
		--out /tmp/preempt_trace_mass.json
	$(PY) tools/preempt_bench.py --trace /tmp/preempt_trace_mass.json \
		--virtual-cpu 4 --flight-dir /tmp/preempt_flight
	$(PY) tools/postmortem.py --dir /tmp/preempt_flight \
		--out /tmp/postmortem_preempt.json
	$(PY) -c "import json; \
		d = json.load(open('/tmp/postmortem_preempt.json')); \
		v = d['verdict']; \
		assert v['failure_kind'] == 'preempted', v; \
		p = d['preempt']; \
		assert p['victims'] and p['zones'], p; \
		assert p['warm_restores'] >= 1, p; \
		print('preempt drill postmortem OK'); \
		print('preempt-smoke OK')"

# resilience smoke: deterministic fault injection + healing/rollback on
# the virtual CPU mesh (kill->heal->contract, NaN->rollback, restart
# supervisor) — the fast chaos tier; heavy chaos runs are marked `slow`
chaos-smoke:
	$(PY) -m pytest tests/test_chaos.py tests/test_resilience.py -q

# autotune smoke: the fast autotune battery (plan determinism, rejection
# audit, cost-model-vs-HLO byte agreement) plus the end-to-end CLI proof —
# tune a restricted space on the virtual CPU mesh, validate the plan
# schema, apply it, train 5 steps with donation, assert zero retraces.
# Live-trial tests are marked `slow` and excluded here.
autotune-smoke:
	$(PY) -m pytest tests/test_autotune.py tests/test_hlo_bytes.py -q \
		-m "not slow"
	$(PY) -m bluefog_tpu.autotune --virtual-cpu --smoke --apply-steps 5 \
		--out /tmp/autotune_plan.json
	$(PY) -c "import json; \
		d = json.load(open('/tmp/autotune_plan.json')); \
		assert d['schema'] == 'bluefog-autotune-plan-1', d; \
		assert all(k in d for k in ('plan_id', 'config', 'objective', \
		'n_chips', 'device_kind', 'predicted', 'audit')), d; \
		cfg = d['config']; \
		assert all(k in cfg for k in ('algorithm', 'topology', 'wire', \
		'weights', 'fused_k', 'delayed', 'concurrent')), cfg; \
		p = d['predicted']; \
		assert p['wire_bytes_per_step_per_chip'] >= 0 and \
		p['spectral_gap'] >= 0, p; \
		a = d['audit']; \
		assert a['considered'] == len(a['scored']) + len(a['rejected']), a; \
		assert all(r['reason'] for r in a['rejected']), a; \
		print('autotune-smoke OK')"

# fleet-view smoke: the gossiped-aggregation pytest battery (the 8-rank
# drill, numpy ground truth through churn, breach-anywhere contracts, the
# endpoint/hygiene/hot-path pins) plus fleet_top against a live estate —
# train with the carrier armed, scrape the tool's own /fleet over HTTP,
# gate on the schema + the zero-retrace/health invariants
fleet-smoke:
	$(PY) -m pytest tests/test_fleetview.py -q -m "not slow"
	$(PY) tools/fleet_top.py --virtual-cpu --once --json \
		--out /tmp/fleet_top_smoke.json
	$(PY) -c "import json; \
		d = json.load(open('/tmp/fleet_top_smoke.json')); \
		assert d['ok'] and d['schema'] == 'bluefog-fleet-1', d; \
		assert d['n'] == 8 and d['seen_ranks'] == list(range(8)), d; \
		st = d['staleness']; \
		assert st['rounds_max'] <= st['bound_rounds'], st; \
		c = d['metrics']['bluefog_train_steps_total']; \
		assert c['kind'] == 'counter' and c['global'] > 0 and \
		len(c['per_rank']) == 8, c; \
		i = d['invariants']; \
		assert i['retraces_after_warmup'] == 0 and i['healthz_ok'] and \
		i['fleet_armed'], i; \
		print('fleet-smoke OK')"

# build the native (C++) components explicitly (otherwise built lazily)
native:
	$(PY) -c "from bluefog_tpu import _native; assert _native.available()"

clean:
	rm -f bluefog_tpu/_native/libbft_native*.so
	find . -name __pycache__ -type d -exec rm -rf {} +

# pod-scale smoke: the hierarchical/two-level battery (schedule compile at
# 4096 ranks, CPU AOT cross-slice byte proofs, auto-hierarchy init) plus the
# consensus-vs-bytes frontier artifact — schema drift in the frontier JSON
# fails here
pod-smoke:
	$(PY) -m pytest tests/test_pod_scale.py -q -m "not slow"
	$(PY) -m pytest tests/test_hierarchical.py tests/test_topology.py -q
	$(PY) tools/gossip_bench.py --frontier --shapes 8x4,16x8 --wire bf16 \
		--out /tmp/gossip_frontier.json
	$(PY) -c "import json; \
		d = json.load(open('/tmp/gossip_frontier.json')); \
		assert d['schema'] == 'bluefog-gossip-frontier-1', d; \
		assert len(d['shapes']) == 2, d; \
		assert all(k in s for s in d['shapes'] for k in ('machines', \
		'local', 'ranks', 'flat', 'hier', 'dcn_ratio', \
		'frontier_ratio')), d; \
		hops = [h for s in d['shapes'] for r in (s['flat'], s['hier']) \
		for h in r['hops']]; \
		assert all(set(h) == {'hop', 'link', 'ici_bytes', 'dcn_bytes'} \
		for h in hops), hops; \
		assert {h['link'] for h in d['shapes'][0]['hier']['hops']} == \
		{'ici', 'dcn'}, d; \
		assert all(s['frontier_ratio'] > 1 for s in d['shapes']), d; \
		print('pod-smoke OK')"

# async-gossip smoke: the bounded-staleness battery (mixing property,
# float64 K=0 oracle, autotune plannability) plus the async frontier
# artifact — one rank throttled 10x, async wall-clock-to-consensus must
# strictly beat sync; schema drift in the frontier JSON fails here
async-smoke:
	$(PY) -m pytest tests/test_async_gossip.py -q -m "not slow"
	$(PY) tools/gossip_bench.py --async-frontier --virtual-cpu \
		--params 2048 --out /tmp/async_frontier.json
	$(PY) -c "import json; \
		d = json.load(open('/tmp/async_frontier.json')); \
		assert d['schema'] == 'bluefog-gossip-async-1', d; \
		assert d['throttle']['factor'] == 10, d; \
		assert d['sync']['reached_target'] and \
		d['async']['reached_target'], d; \
		assert all(k in d['async'] for k in ('ticks', 'wall_s', \
		'forced_syncs', 'staleness_max')), d; \
		assert d['won'] is True and d['speedup'] > 1.0, d; \
		print('async-smoke OK')"
