# Convenience targets; everything also works without make (README).
.PHONY: test native bench analyze wirecheck serve-smoke serve-dist-smoke workloads-smoke workloads-dist-smoke chaos-smoke mesh-chaos-smoke integrity-smoke cache-smoke obs-smoke preheat-smoke mutation-smoke wheel clean

# Full suite on 8 virtual CPU devices (tests/conftest.py forces the
# platform). The chip run is `python chip_smoke.py` through the chip tool.
test:
	python -m pytest tests/ -x -q

# Optional C++ fast paths (loader + RMAT generator); NumPy fallbacks
# otherwise. Also built on demand by tpu_bfs/utils/native.py.
native:
	$(MAKE) -C tpu_bfs/native

# One-line JSON benchmark on the attached accelerator (env knobs in
# bench.py's docstring; outage envelope guarantees the line lands).
bench:
	python bench.py

# Static verification (README "Static analysis"; tpu_bfs/analysis): the
# seven-pass sweep over every distributed engine config — collective-
# uniformity taint + compiled-HLO conditional signatures (a divergent
# branch selection deadlocks a real mesh; invisible on single-host CPU
# tests), the transfer/retrace guards (no host round-trips in hot loops,
# no shape-driven recompiles on the serve path, lazy distance contract),
# the guarded-by/lock-order AST lint over serve/ + obs/, the 64-bit
# dtype lint, the static HBM budget (per-program peak estimates, the
# strictly-monotone ladder model, the buffer-donation lint + HLO alias
# certificates), the exception-path lifecycle walk (spans/locks/resume
# snapshots closed on every path incl. raises), and the fault-site
# coverage audit (faults.SITES vs consults vs test coverage). Findings
# gate on the analysis-baseline.txt suppression file; exit 1 on
# anything new (--json emits the machine-readable report the
# chip-session pre-flight consumes). CPU-only, like wirecheck — and a
# prerequisite OF wirecheck (and so of every smoke target): a program
# that can deadlock the mesh must fail before its byte model is even
# worth auditing.
analyze:
	env JAX_PLATFORMS=cpu python -m tpu_bfs.analysis --baseline analysis-baseline.txt

# Byte-model vs compiled-HLO audit (fast, CPU-only, 8 virtual devices):
# every wire-byte formula the framework prints is re-derived from the
# compiled program's own collective shapes — the ISSUE 5 packed-exchange
# proof (uint32 words = 1/8 the ring bytes, 1/32 the allreduce operand,
# zero extra collectives), the ISSUE 7 sparse-format proofs (delta
# branches ship 1 + ceil(cap*b/32) uint32 words per destination, the
# sieve adds EXACTLY ONE packed vis all-gather, the 2D sparse row
# exchange and the MS row-gather delta stream price to their models),
# and the codec/planner property tests. A model regression fails HERE,
# before a chip session ever spends hardware time on it; hence it is
# also a prerequisite of the smoke targets.
wirecheck: analyze
	env JAX_PLATFORMS=cpu python -m pytest tests/test_wirecheck.py \
	  tests/test_collectives_pack.py -q -p no:cacheprovider

# Round-trip 4 queries through the JSONL serving frontend on CPU
# (tpu_bfs/serve; README "Serving mode") over a 2-width ladder, so the
# adaptive routing + pipelined extraction path runs in CI, not just on
# chip; checks the distance payloads decode and that a
# want_distances=false request answers metadata-only.
serve-smoke: wirecheck
	printf '{"id":1,"source":0}\n{"id":2,"source":3}\n{"id":3,"source":5}\n{"id":4,"source":5,"want_distances":false}\n' | \
	env JAX_PLATFORMS=cpu python -m tpu_bfs.serve random:n=96,m=480,seed=3 \
	  --lanes 64 --ladder 32,64 --linger-ms 1 --statsz-every 0 | \
	python -c "import sys, json; \
	from tpu_bfs.serve.frontend import decode_distances; \
	rs = [json.loads(l) for l in sys.stdin if l.strip()]; \
	assert len(rs) == 4 and all(r['status'] == 'ok' for r in rs), rs; \
	assert all(r['dispatched_lanes'] == 32 for r in rs), rs; \
	withd = [r for r in rs if r['id'] != 4]; \
	assert all(int(decode_distances(r['distances_npy'])[r['source']]) == 0 for r in withd), rs; \
	meta = [r for r in rs if r['id'] == 4][0]; \
	assert 'distances_npy' not in meta and meta['levels'] >= 1, rs; \
	print('serve-smoke OK:', sorted(r['id'] for r in rs))"

# Distributed-serving smoke (README "Distributed serving"; ISSUE 11):
# a JSONL round trip against a MESH-backed service on the forced
# 8-device CPU mesh — the frontend dispatches coalesced batches through
# the distributed wide engine's dispatch/fetch halves, responses carry
# the mesh keys (devices, per-query gteps, wire_bytes), distance
# payloads decode, and a want_distances=false request answers
# metadata-only straight off the on-device summaries.
serve-dist-smoke: wirecheck
	printf '{"id":1,"source":0}\n{"id":2,"source":3}\n{"id":3,"source":5}\n{"id":4,"source":5,"want_distances":false}\n' | \
	env JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	  python -m tpu_bfs.serve random:n=96,m=480,seed=3 \
	  --engine wide --devices 8 --lanes 64 --ladder off --linger-ms 1 \
	  --statsz-every 0 | \
	python -c "import sys, json; \
	from tpu_bfs.serve.frontend import decode_distances; \
	rs = [json.loads(l) for l in sys.stdin if l.strip()]; \
	assert len(rs) == 4 and all(r['status'] == 'ok' for r in rs), rs; \
	assert all(r['devices'] == 8 for r in rs), rs; \
	assert all(r['dispatched_lanes'] == 64 for r in rs), rs; \
	assert all(r.get('gteps', 0) > 0 and r.get('wire_bytes', 0) > 0 for r in rs), rs; \
	withd = [r for r in rs if r['id'] != 4]; \
	assert all(int(decode_distances(r['distances_npy'])[r['source']]) == 0 for r in withd), rs; \
	meta = [r for r in rs if r['id'] == 4][0]; \
	assert 'distances_npy' not in meta and meta['levels'] >= 1, rs; \
	print('serve-dist-smoke OK:', sorted(r['id'] for r in rs))"

# The workload-kind smoke (README "Workload kinds"; ISSUE 14): a 4-kind
# JSONL round trip — sssp (weighted distances, dijkstra-exact), cc
# (component label/size/count), khop (k-hop count off the on-device
# summaries, no distance payload), and p2p (bidirectional shortest path
# with the reconstructed vertex path) — against one service over a
# weighted graph, plus an unknown-kind request answered with a
# structured per-id error. Runs after analyze/wirecheck like every
# smoke: the kind axis must be statically clean before it serves.
workloads-smoke: wirecheck
	printf '{"id":1,"source":0,"kind":"sssp"}\n{"id":2,"source":0,"kind":"cc"}\n{"id":3,"source":0,"kind":"khop","k":2}\n{"id":4,"source":0,"kind":"p2p","target":5}\n{"id":5,"source":0,"kind":"nope"}\n' | \
	env JAX_PLATFORMS=cpu python -m tpu_bfs.serve random:n=96,m=480,seed=3,weights=5 \
	  --lanes 32 --ladder off --linger-ms 1 --statsz-every 0 | \
	python -c "import sys, json; \
	from tpu_bfs.serve.frontend import decode_distances; \
	rs = {r['id']: r for l in sys.stdin if l.strip() for r in [json.loads(l)]}; \
	assert len(rs) == 5, sorted(rs); \
	assert rs[1]['status'] == 'ok' and rs[1]['kind'] == 'sssp', rs[1]; \
	assert int(decode_distances(rs[1]['distances_npy'])[0]) == 0, rs[1]; \
	assert rs[2]['status'] == 'ok' and rs[2]['components'] >= 1 and rs[2]['component_size'] == rs[2]['reached'], rs[2]; \
	assert rs[3]['status'] == 'ok' and rs[3]['k'] == 2 and 'distances_npy' not in rs[3], rs[3]; \
	assert rs[4]['status'] == 'ok' and rs[4]['target'] == 5 and (rs[4]['path'] is None or rs[4]['path'][0] == 0), rs[4]; \
	assert rs[5]['status'] == 'error' and 'unknown kind' in rs[5]['error'], rs[5]; \
	print('workloads-smoke OK:', sorted(rs))"

# The mesh workload-kind smoke (README "Workload kinds"; ISSUE 20): the
# same 4-kind JSONL round trip served over the FULL 8-virtual-device
# CPU mesh with the (min,+)-capable sparse exchange — sssp rides the
# sharded min-plus delta-stepping tiles, cc the distributed min-label
# fold, khop/p2p the dist cores' dispatch protocol — plus an
# unknown-kind request whose structured error names WHY. Runs after
# analyze/wirecheck: the min-plus exchange byte model must be
# HLO-proven before the mesh serves values.
workloads-dist-smoke: wirecheck
	printf '{"id":1,"source":0,"kind":"sssp"}\n{"id":2,"source":0,"kind":"cc"}\n{"id":3,"source":0,"kind":"khop","k":2}\n{"id":4,"source":0,"kind":"p2p","target":5}\n{"id":5,"source":0,"kind":"nope"}\n' | \
	env JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	python -m tpu_bfs.serve random:n=96,m=480,seed=3,weights=5 \
	  --lanes 32 --devices 8 --exchange sparse --sparse-delta 8,16 \
	  --ladder off --linger-ms 1 --statsz-every 0 | \
	python -c "import sys, json; \
	from tpu_bfs.serve.frontend import decode_distances; \
	rs = {r['id']: r for l in sys.stdin if l.strip() for r in [json.loads(l)]}; \
	assert len(rs) == 5, sorted(rs); \
	assert rs[1]['status'] == 'ok' and rs[1]['kind'] == 'sssp', rs[1]; \
	assert int(decode_distances(rs[1]['distances_npy'])[0]) == 0, rs[1]; \
	assert rs[2]['status'] == 'ok' and rs[2]['components'] >= 1 and rs[2]['component_size'] == rs[2]['reached'], rs[2]; \
	assert rs[3]['status'] == 'ok' and rs[3]['k'] == 2 and 'distances_npy' not in rs[3], rs[3]; \
	assert rs[4]['status'] == 'ok' and rs[4]['target'] == 5 and (rs[4]['path'] is None or rs[4]['path'][0] == 0), rs[4]; \
	assert rs[5]['status'] == 'error' and 'unknown kind' in rs[5]['error'], rs[5]; \
	print('workloads-dist-smoke OK:', sorted(rs))"

# The seeded chaos soak (README "Failure model"): a JSONL server under a
# deterministic fault schedule (transient + OOM degrade + slow extract)
# must answer bit-identically to the fault-free run with every injected
# fault visible in statsz; SIGTERM mid-stream must drain cleanly; and a
# corrupted checkpoint save must quarantine + fall back on load. The
# pytest `chaos` marker runs the same machinery in-process
# (tests/test_chaos.py, tests/test_faults.py).
chaos-smoke: wirecheck
	env JAX_PLATFORMS=cpu python scripts/chaos_smoke.py

# The MESH-chaos soak (README "Failure model", ISSUE 12): an injected
# device_lost MID-QUERY on the forced 8-device CPU mesh must run the
# degraded-mesh failover ladder (8 -> 4 devices), resume the faulted
# queries from their level checkpoints (bounded recompute), and answer
# every query bit-identically to the fault-free run with NO
# client-visible error — mesh_faults/mesh_degrades/query_resumes
# audited in the final statsz and the flight recorder dumping an
# artifact that names the fault; plus a fleet-supervisor act (SIGKILL
# one replica mid-stream -> requeue onto the sibling). The pytest
# `chaos` marker runs the same machinery in-process
# (tests/test_mesh_chaos.py, tests/test_warm_handoff.py).
mesh-chaos-smoke: chaos-smoke
	env JAX_PLATFORMS=cpu python scripts/mesh_chaos_smoke.py

# The integrity soak (README "Result integrity", ISSUE 15): a fully-
# audited server (shadow rate 1.0 + structural tree checks + wire
# checksums) must answer a clean mixed-kind stream with ZERO audit
# findings; then, with corrupt_result armed, the audit tier must catch
# the seeded bit-flip, quarantine the serving rung (eviction + forced-
# open breaker), dump a flight-recorder artifact naming the corrupted
# query, and serve every later query bit-identical to the oracle. The
# pytest side runs the same machinery in-process (tests/test_integrity
# .py + the per-kind corruption fuzz arm in test_fuzz_cross_engine.py).
integrity-smoke: mesh-chaos-smoke
	env JAX_PLATFORMS=cpu python scripts/integrity_smoke.py

# The answer-tier soak (README "Answer cache and landmarks", ISSUE 18):
# a cache+landmark-armed server must serve repeated queries without
# re-traversing (cache hits / single-flight collapses, bit-identical to
# the first traversal and the CPU oracle) and answer landmark-exact p2p
# queries in the submit path; with corrupt_cache_entry armed the CRC32
# check must evict the rotten entry and fall back to a clean traversal;
# with stale_cache armed the shadow audit must quarantine the cache
# GENERATION (never a rung) and the repeat must miss and traverse
# oracle-exact. The pytest side runs the same machinery in-process
# (tests/test_answercache.py + the Zipfian cache-on-vs-off arm in
# test_fuzz_cross_engine.py).
cache-smoke: wirecheck
	env JAX_PLATFORMS=cpu python scripts/cache_smoke.py

# The dynamic-graph soak (README "Dynamic graphs", ISSUE 19): a
# mutation-armed server with the full audit battery live must answer a
# query stream interleaved with edge-update batches bit-identically to
# a from-scratch rebuild of every generation (bfs AND sssp, zero
# dropped queries, zero audit findings); with compaction_crash armed
# the dead compactor's uncommitted artifact must be quarantined
# .corrupt, the flight recorder must name it, and the previous
# generation must keep serving until the retried batch compacts clean;
# with torn_flip armed the staleness auditor's oracle replay must
# confirm the over-bound answer, quarantine the stale generation, heal
# by restaging, and indict NO rung. The pytest side runs the same
# machinery in-process (tests/test_dynamic.py + the interleaved
# mutate/query fuzz arm in test_fuzz_cross_engine.py).
mutation-smoke: cache-smoke
	env JAX_PLATFORMS=cpu python scripts/mutation_smoke.py

# The telemetry smoke (README "Observability"): a tracing-armed JSONL
# server must emit a Perfetto trace holding the FULL span chain of every
# query id (admit -> coalesce -> dispatch -> fetch -> extract -> resolve)
# plus the per-level engine-trace track and a /metricz text that agrees
# with statsz; the chaos variant injects a watchdog trip and asserts the
# flight recorder dumps a replayable artifact naming the fault's site.
# The pytest `obs` marker runs the same layer in-process
# (tests/test_obs.py — including the disarmed-path zero-overhead spies).
obs-smoke: wirecheck
	env JAX_PLATFORMS=cpu python scripts/obs_smoke.py

# The cold-start smoke (README "Cold start and preheat"): a warmed JSONL
# server exports its compiled programs (--export-aot) into an artifact
# store; a SECOND process preheats from it (--preheat) and must reach
# READY with 10/10 artifact hits, answer bit-identically to the JIT
# baseline, and show engine_adopt spans with ZERO engine_build spans in
# its own Perfetto trace; then the warm-handoff driver
# (scripts/warm_handoff.py) proves the old server is SIGTERM-drained
# only AFTER the preheated successor reports ready. The pytest side
# (tests/test_aot.py) runs the store/fingerprint/CRC arms in-process.
preheat-smoke: wirecheck
	env JAX_PLATFORMS=cpu python scripts/preheat_smoke.py

wheel:
	python -m pip wheel . --no-deps --no-build-isolation -w dist

clean:
	rm -rf build dist *.egg-info tpu_bfs/native/build
