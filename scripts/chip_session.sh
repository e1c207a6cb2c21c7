#!/bin/bash
# One opportunistic TPU session: whenever the chip comes back, take the
# round's measurements in priority order and stop. Each stage's stdout is
# preserved under .bench_cache/chip_session/. Retries the whole sequence
# until the flagship number lands or the attempt budget runs out (the
# bench's own retry ladder handles intra-run blips; this loop handles
# multi-hour outages).
#
# The bench's outage envelope (TPU_BFS_BENCH_BUDGET_S, default 1200 s)
# makes each attempt terminate with one JSON line; a run that measured
# nothing prints value=null and exits nonzero. Every stage's JSON is
# checked for a non-null value (scripts/has_value.py).
#
# Superseded as the first chip step by chip_smoke.py (PR 21); kept for the
# sweep arms below until the benchmark PR defines the cells.
#
# Since round 4 the bench DEFAULTS are the measured-best configuration
# (8192 lanes + level-adaptive push), so the headline "flagship" stage
# runs plain `python bench.py`, and the comparison arms pin their env
# explicitly — each stage measures exactly what its name claims:
#   flagship            defaults (8192 lanes, adaptive push)
#   flagship-noadaptive TPU_BFS_BENCH_ADAPTIVE=0      — the push A/B arm
#   width-4096-plain    + TPU_BFS_BENCH_MAX_LANES=4096 — the width A/B arm
#                         (also the round-1..3 historical series config)
#   lj-hybrid           defaults on the LiveJournal-shaped stand-in
#   kcap-32/kcap-128    residual ELL bucket-cap sweep     (TPU_BFS_BENCH_KCAP)
#   thr32-b08/thr128    dense-tile threshold/budget sweep (TILE_THR/A_BUDGET)
# (The former adaptive_stage.sh follow-on is folded in as the
# flagship-noadaptive arm: the round-4 keep-or-kill measured 62.21 GTEPS
# adaptive vs 55.96 plain and adaptive became the default.)
set -u
out=.bench_cache/chip_session
attempts="${CHIP_SESSION_ATTEMPTS:-12}"
mkdir -p "$out"

got_value() {  # true iff $1 ends with a JSON line carrying a non-null value
  python scripts/has_value.py "$1"
}

stage() {  # stage <name> <json-out> [ENV=VAL...] — one bench.py run
  local name="$1" json="$2"; shift 2
  if [ -s "$json" ] && got_value "$json"; then
    echo "$name already landed: $(tail -1 "$json")"   # idempotent restart
    return 0
  fi
  echo "=== $name $(date -u +%H:%M:%S) ==="
  # This script runs as the builder's own nohup'd background session —
  # NOT under the driver's ~30-40 min bench window (which only applies
  # to the driver's end-of-round `python bench.py`). Unsupervised, the
  # bench's 1200 s driver-sized default budget would cut an attempt 20
  # min into an init poll even if the chip frees at minute 19, so stages
  # default to two full init-poll windows. Precedence: CHIP_SESSION_BUDGET_S
  # > an operator-exported TPU_BFS_BENCH_BUDGET_S (bench.py's documented
  # remedies — raising it, or =0 debug mode — must keep working) > 3600;
  # later "$@" env wins over all, so per-stage overrides remain possible.
  if env TPU_BFS_BENCH_BUDGET_S="${CHIP_SESSION_BUDGET_S:-${TPU_BFS_BENCH_BUDGET_S:-3600}}" "$@" \
      python bench.py >"$json" 2>"${json%.json}.log" \
      && got_value "$json"; then
    echo "$name OK: $(tail -1 "$json")"
    return 0
  fi
  echo "$name FAILED (see ${json%.json}.log): $(tail -1 "$json" 2>/dev/null)"
  return 1
}

pstage() {  # pstage <name> <json-out> <script> [ENV=VAL...] — one helper-script run
  local name="$1" json="$2" script="$3"; shift 3
  if [ -s "$json" ] && got_value "$json"; then
    echo "$name already landed: $(tail -1 "$json")"
    return 0
  fi
  echo "=== $name $(date -u +%H:%M:%S) ==="
  # Helper scripts have no outage envelope of their own (they never arm
  # bench.py's watchdog), so a chip drop mid-script would otherwise wedge
  # the whole session on one unbudgeted attempt. timeout(1) is that
  # envelope here: on expiry the stage FAILS and the slate moves on.
  if timeout "${CHIP_SESSION_PSTAGE_TIMEOUT_S:-5400}" \
      env "$@" python "$script" >"$json" 2>"${json%.json}.log" \
      && got_value "$json"; then
    echo "$name OK: $(tail -1 "$json")"
    return 0
  fi
  echo "$name FAILED (see ${json%.json}.log): $(tail -1 "$json" 2>/dev/null)"
  return 1
}

# Pre-flight (ISSUE 8): the static analyzer runs on CPU BEFORE any A/B
# stage burns chip time — a mesh program whose branch selection can
# diverge across ranks would hang a real multi-chip stage mid-BFS (the
# failure class single-host CPU tests cannot see), and a serve-path
# retrace or hot-loop host sync would poison every timing the session
# collects. Fail fast here, while the only cost is seconds of CPU.
echo "=== analyze pre-flight $(date -u +%H:%M:%S) ==="
# The analyzer's --json report (ISSUE 13) is the machine-readable
# contract: the gate below reads verdicts and finding counts from
# $out/analyze.json instead of scraping exit text, and the artifact
# rides with the stage outputs (per-pass certificates included — the
# per-program peak-HBM estimates and the ladder monotonicity proof).
env JAX_PLATFORMS=cpu python -m tpu_bfs.analysis --json \
    --baseline analysis-baseline.txt \
    >"$out/analyze.json" 2>"$out/analyze.log"
analyze_rc=$?
analyze_verdict=$(python - "$out/analyze.json" <<'PYEOF'
import json, sys
try:
    rep = json.load(open(sys.argv[1]))
except Exception as exc:  # unparsable report = failed pre-flight
    print(f"unreadable:{exc}")
    raise SystemExit(0)
print(
    f"ok={rep.get('ok')} new={len(rep.get('findings', []))} "
    f"suppressed={len(rep.get('suppressed', []))} "
    f"stale={len(rep.get('stale_baseline', []))}"
)
PYEOF
)
echo "analyze: $analyze_verdict"
if [ "$analyze_rc" -ne 0 ] || ! printf '%s' "$analyze_verdict" | grep -q '^ok=True'; then
  echo "static analysis FAILED (see $out/analyze.json / analyze.log) — not burning chip time"
  exit 1
fi
echo "analyze pre-flight OK"

for i in $(seq 1 "$attempts"); do
  echo "=== attempt $i $(date -u +%H:%M:%S) ==="
  if stage "flagship" "$out/flagship.json"; then
    # Round-5 slate in VERDICT r4 priority order — a short chip window
    # should land the round's NEW measurements before re-confirmations:
    # structure sweep at the 8192+push operating point + the
    # floor-subtracted 256/512-word gather probe (#2), roofline
    # attribution (#3), device parent scan at flagship scale (#4), the
    # 16384-lane arm at scale 20 (plain, matching the width series'
    # historical config; #5), a quiet-chip tiled single-stream run (#7),
    # the scale-22 auto-walk OOM-edge rehearsal (weak #6), then the
    # round-4 re-confirmation arms (their figures are already in the
    # durable log).
    stage "kcap-32" "$out/kcap32.json" TPU_BFS_BENCH_KCAP=32
    stage "kcap-128" "$out/kcap128.json" TPU_BFS_BENCH_KCAP=128
    stage "thr32-b08" "$out/thr32_b08.json" \
      TPU_BFS_BENCH_TILE_THR=32 TPU_BFS_BENCH_A_BUDGET=8e8
    stage "thr128" "$out/thr128.json" TPU_BFS_BENCH_TILE_THR=128
    # Pull-gate A/B (ISSUE 1): gated arms at scale 21 and 20 against
    # plain (no adaptive push on either side, so the pairs isolate the
    # gate; the flagship-noadaptive arm below is the scale-21 baseline
    # and plain-s20 the scale-20 one). The gated runs ride the bench's
    # own budget envelope like every stage; their JSON lines carry the
    # per-level gate_level_counts the byte model is checked against.
    stage "pullgate-s21" "$out/pullgate_s21.json" \
      TPU_BFS_BENCH_PULL_GATE=1 TPU_BFS_BENCH_ADAPTIVE=0
    stage "pullgate-s20" "$out/pullgate_s20.json" \
      TPU_BFS_BENCH_SCALE=20 TPU_BFS_BENCH_PULL_GATE=1 \
      TPU_BFS_BENCH_ADAPTIVE=0
    stage "plain-s20" "$out/plain_s20.json" \
      TPU_BFS_BENCH_SCALE=20 TPU_BFS_BENCH_ADAPTIVE=0
    # Pallas expansion-tier A/B (ISSUE 16, default OFF until these land):
    # the fused bucketed-ELL kernel vs the fori form XLA fuses, at scale
    # 21 and 20 against the same no-adaptive baselines as the pull-gate
    # pairs (flagship-noadaptive / plain-s20). Bit-identical output
    # (fuzz-pinned); the JSON lines carry expand_impl and the modeled
    # per-level kernel bytes the roofline's VMEM-resident bound prices.
    stage "pallas-expand-s21" "$out/pallas_expand_s21.json" \
      TPU_BFS_BENCH_EXPAND_IMPL=pallas TPU_BFS_BENCH_ADAPTIVE=0
    stage "pallas-expand-s20" "$out/pallas_expand_s20.json" \
      TPU_BFS_BENCH_SCALE=20 TPU_BFS_BENCH_EXPAND_IMPL=pallas \
      TPU_BFS_BENCH_ADAPTIVE=0
    # Serve-throughput A/B (ISSUE 3): the closed-loop lane-batching
    # query server at scale 20, adaptive (width ladder + pipelined
    # extraction — the defaults) vs fixed (one width, inline extraction
    # — the PR-2 behavior). The pair isolates the adaptive dispatch win:
    # compare serve_qps/serve_p99_ms/serve_extract_p50_ms across the two
    # JSONs; serve_routing in the adaptive line shows where batches
    # actually landed on the ladder.
    stage "serve-adaptive-s20" "$out/serve_adaptive_s20.json" \
      TPU_BFS_BENCH_MODE=serve TPU_BFS_BENCH_SCALE=20
    stage "serve-fixed-s20" "$out/serve_fixed_s20.json" \
      TPU_BFS_BENCH_MODE=serve TPU_BFS_BENCH_SCALE=20 \
      TPU_BFS_BENCH_SERVE_LADDER=off TPU_BFS_BENCH_SERVE_PIPELINE=0
    # Workload-kind arm (ISSUE 14): the mixed-kind closed loop — bfs +
    # sssp (delta-stepping over the weighted tiles) + cc + khop + p2p
    # interleaved through the kind-aware coalescer on chip. The graph
    # gains its deterministic weight plane in-place; per-kind p50/p99
    # land under serve_kinds and serve_kinds_qps prices the mixed
    # stream (BENCHMARKS.md "Workload kinds").
    stage "workloads-s20" "$out/workloads_s20.json" \
      TPU_BFS_BENCH_MODE=serve TPU_BFS_BENCH_SCALE=20 \
      TPU_BFS_BENCH_SERVE_KINDS=all
    # Distributed-kind arm (ISSUE 20): every workload kind over the
    # FULL attached mesh — sssp on the sharded min-plus delta-stepping
    # tiles, cc on the dist min-label fold, khop/p2p on the dist cores'
    # protocol, all through the sparse value exchange. Per-kind p50 /
    # gteps_hmean / wire_bytes_per_query plus the modeled labelled
    # wire_bytes_per_level table land under dist_kinds (BENCHMARKS.md
    # "Exchange bytes").
    stage "workloads-dist-s20" "$out/workloads_dist_s20.json" \
      TPU_BFS_BENCH_MODE=serve TPU_BFS_BENCH_SCALE=20 \
      TPU_BFS_BENCH_DIST_KINDS=all
    # Chaos arm (robustness): the same closed-loop serve stage under a
    # seeded fault schedule (tpu_bfs/faults.py) — injected transients and
    # slowed extraction ON CHIP must not change a single answer (the
    # stage's own oracle validation) and the recovery/fault counters ride
    # the JSON line (serve_faults / serve_watchdog_trips / recovery).
    stage "chaos-s20" "$out/chaos_s20.json" \
      TPU_BFS_BENCH_MODE=serve TPU_BFS_BENCH_SCALE=20 \
      TPU_BFS_BENCH_FAULTS="seed=7:transient@serve_batch:n=2,slow_extract:ms=50:n=4" \
      TPU_BFS_BENCH_SERVE_WATCHDOG_MS=600000
    # Mesh-chaos arm (robustness, ISSUE 12): the dist2d serve stage
    # across the full mesh with an injected device_lost MID-QUERY (the
    # level=2 chunk of a level-checkpointed traversal; skip=1 spares the
    # warm-up's visit). The service must run the failover ladder (full
    # mesh -> half mesh), resume from the level checkpoints, and answer
    # every query correctly — serve_mesh_faults/serve_mesh_degrades/
    # serve_query_resumes ride the JSON line and serve_devices_final
    # records the degraded width the stage ended on. ON CHIP this is the
    # r03/r04 outage class replayed deliberately.
    stage "mesh-chaos-s20" "$out/mesh_chaos_s20.json" \
      TPU_BFS_BENCH_MODE=serve TPU_BFS_BENCH_SCALE=20 \
      TPU_BFS_BENCH_SERVE_DEVICES=all TPU_BFS_BENCH_SERVE_ENGINE=dist2d \
      TPU_BFS_BENCH_SERVE_LANES=64 TPU_BFS_BENCH_SERVE_RESUME=2 \
      TPU_BFS_BENCH_FAULTS="seed=3:device_lost@fetch@level=2:n=1:skip=1"
    # Integrity arm (robustness, ISSUE 15): the same closed-loop serve
    # stage with the online audit tier armed at the production operating
    # point — shadow re-execution of 10% of resolved queries on a
    # disjoint ladder rung, structural tree checks on every batch, wire
    # checksums on the audited transfers. Acceptance: ZERO
    # serve_audit_failures on clean hardware and <5% serve_p50_ms
    # regression vs serve-adaptive-s20 (the audits ride the extraction
    # worker and a background thread, never the dispatch path);
    # serve_audits_run / serve_audit_p50_lag_ms price the tier ON CHIP.
    stage "integrity-s20" "$out/integrity_s20.json" \
      TPU_BFS_BENCH_MODE=serve TPU_BFS_BENCH_SCALE=20 \
      TPU_BFS_BENCH_SERVE_AUDIT_RATE=0.1 \
      TPU_BFS_BENCH_SERVE_AUDIT_CHECKSUM=1
    # Answer-tier arm (perf, ISSUE 18): the same serve stage with the
    # result cache (64 MB default budget) and the 16-column landmark
    # index armed; after the uniform loop a Zipf(s=1.0) closed loop
    # over the degree-ranked hot set measures how much of a skewed
    # stream resolves WITHOUT traversing. Acceptance:
    # serve_cache_hit_rate + serve_landmark_hit_rate > 0.5 and
    # serve_hit_p50_ms at least 10x below serve_traversal_p50_ms (the
    # hit path is a dict probe + CRC check / a NumPy column gather —
    # microseconds against the batch pipeline's milliseconds).
    stage "cache-s20" "$out/cache_s20.json" \
      TPU_BFS_BENCH_MODE=serve TPU_BFS_BENCH_SCALE=20 \
      TPU_BFS_BENCH_SERVE_CACHE=1 \
      TPU_BFS_BENCH_SERVE_LANDMARKS=16
    # Dynamic-graph arm (robustness, ISSUE 19): the same serve stage
    # with the bounded delta overlay armed — 16 streaming edge-update
    # flips land WHILE the closed loop keeps querying. Acceptance:
    # serve_mutation_dropped == 0 across every generation flip,
    # serve_flip_p50_ms well under the batch latency (the flip is a
    # lock-guarded metadata swap, not a rebuild), and the overlay
    # occupancy/compaction record rides the same JSON line.
    stage "mutations-s20" "$out/mutations_s20.json" \
      TPU_BFS_BENCH_MODE=serve TPU_BFS_BENCH_SCALE=20 \
      TPU_BFS_BENCH_MUTATIONS=16
    # Cold-start arm (ISSUE 9): the same serve stage with an AOT
    # artifact store armed — the cold service's warmed programs export
    # to $out/aot_store after the closed loop, a SECOND service preheats
    # from it, and serve_cold_start_s vs serve_preheat_s land side by
    # side in one JSON line (plus the aot_hits/aot_fallbacks audit:
    # fallbacks must be 0 on a same-chip rerun, and a jax/runtime
    # upgrade shows up as fallbacks, not wrong answers). The store is
    # per-session scratch; a stale one from an earlier software stack
    # degrades to JIT by fingerprint.
    stage "serve-preheat-s20" "$out/serve_preheat_s20.json" \
      TPU_BFS_BENCH_MODE=serve TPU_BFS_BENCH_SCALE=20 \
      TPU_BFS_BENCH_AOT_DIR="$out/aot_store"
    # Telemetry arm (ISSUE 6): the same serve stage with the obs
    # recorder on — the JSON line gains serve_obs_events/serve_trace and
    # a Perfetto trace of the whole on-chip serving session lands next to
    # the stage output (load it at ui.perfetto.dev; README
    # "Observability"). A/B against serve-adaptive-s20 prices the armed
    # recorder's overhead on real hardware (<2% is the acceptance bar).
    stage "obs-s20" "$out/obs_s20.json" \
      TPU_BFS_BENCH_MODE=serve TPU_BFS_BENCH_SCALE=20 \
      TPU_BFS_BENCH_OBS="dump_dir=$out" \
      TPU_BFS_BENCH_TRACE_OUT="$out/obs_s20_trace.json"
    # Distributed serving (ISSUE 11): the serve frontend dispatching
    # coalesced batches through the DISTRIBUTED engines across the full
    # attached mesh. serve-dist-s20 is the hybrid-mesh baseline;
    # serve-dist-pullgate-s20 is the pull-gate A/B arm ON THE SERVE PATH
    # — together with pullgate-s21/s20 this is the slate that finally
    # decides the pull_gate default (ON if the gated arms win both the
    # one-shot and served shapes; it has defaulted OFF since PR 1
    # awaiting exactly this measurement). serve-dist2d-s20 /
    # serve-dist2d-packed-s20 run the 2D engine plain vs bit-packed on
    # both its per-level collectives — the wire_pack decision pair (OFF
    # since PR 5 awaiting chip measurement; the MS engines' lane words
    # are already packed, so the 2D pair is where packing can actually
    # move bytes on the serve path). Every line carries per-query GTEPS
    # (p50 + hmean) and modeled wire bytes per query.
    stage "serve-dist-s20" "$out/serve_dist_s20.json" \
      TPU_BFS_BENCH_MODE=serve TPU_BFS_BENCH_SCALE=20 \
      TPU_BFS_BENCH_SERVE_DEVICES=all TPU_BFS_BENCH_SERVE_ENGINE=hybrid \
      TPU_BFS_BENCH_SERVE_LANES=4096
    stage "serve-dist-pullgate-s20" "$out/serve_dist_pullgate_s20.json" \
      TPU_BFS_BENCH_MODE=serve TPU_BFS_BENCH_SCALE=20 \
      TPU_BFS_BENCH_SERVE_DEVICES=all TPU_BFS_BENCH_SERVE_ENGINE=hybrid \
      TPU_BFS_BENCH_SERVE_LANES=4096 TPU_BFS_BENCH_SERVE_PULL_GATE=1
    stage "serve-dist2d-s20" "$out/serve_dist2d_s20.json" \
      TPU_BFS_BENCH_MODE=serve TPU_BFS_BENCH_SCALE=20 \
      TPU_BFS_BENCH_SERVE_DEVICES=all TPU_BFS_BENCH_SERVE_ENGINE=dist2d \
      TPU_BFS_BENCH_SERVE_LANES=64
    stage "serve-dist2d-packed-s20" "$out/serve_dist2d_packed_s20.json" \
      TPU_BFS_BENCH_MODE=serve TPU_BFS_BENCH_SCALE=20 \
      TPU_BFS_BENCH_SERVE_DEVICES=all TPU_BFS_BENCH_SERVE_ENGINE=dist2d \
      TPU_BFS_BENCH_SERVE_LANES=64 TPU_BFS_BENCH_WIRE_PACK=1
    # THE exit demonstration (ROADMAP item 1 / PAPER.md target): a
    # correct Graph500 scale-26 BFS answered by a serve frontend across
    # the full mesh, per-query GTEPS on the line. Validation is the
    # Graph500 structural check (source at 0, edge levels within 1) —
    # the SciPy oracle cannot hold a scale-26 graph. Small closed loop:
    # the point is the scale, not the QPS.
    stage "graph500-s26" "$out/graph500_s26.json" \
      TPU_BFS_BENCH_MODE=serve TPU_BFS_BENCH_SCALE=26 \
      TPU_BFS_BENCH_SERVE_DEVICES=all TPU_BFS_BENCH_SERVE_ENGINE=hybrid \
      TPU_BFS_BENCH_SERVE_LANES=4096 TPU_BFS_BENCH_SERVE_CLIENTS=16 \
      TPU_BFS_BENCH_SERVE_QUERIES=2 TPU_BFS_BENCH_SERVE_EXCHANGE=sliced \
      TPU_BFS_BENCH_VALIDATE_MODE=structure \
      TPU_BFS_BENCH_VALIDATE_LANES=2
    # Wire-format A/B (ISSUE 5): the 1D distributed exchange bit-packed
    # (TPU_BFS_BENCH_WIRE_PACK=1: uint32 words, 1 bit/vertex on the wire
    # — wirecheck-proven 1/8 the ring bytes) vs plain (pred ring) at
    # scale 20 — packing defaults OFF until chip-measured, like the pull
    # gate, so the plain arm is today's behavior. Each JSON line carries
    # wire_bytes_per_level / wire_level_counts / wire_bytes_total for the
    # BENCHMARKS.md "Exchange bytes" table. On a 1-chip attachment the
    # pair still lands (wire keys zero; the A/B then only prices the
    # pack/unpack compute).
    stage "dist-plain-s20" "$out/dist_plain_s20.json" \
      TPU_BFS_BENCH_MODE=dist TPU_BFS_BENCH_SCALE=20
    stage "dist-packed-s20" "$out/dist_packed_s20.json" \
      TPU_BFS_BENCH_MODE=dist TPU_BFS_BENCH_SCALE=20 \
      TPU_BFS_BENCH_WIRE_PACK=1
    # Sparse-format A/B (ISSUE 7): the queue-style exchange plain, with
    # delta-encoded id chunks, and with the full planner (delta + the
    # backward visited sieve + history-predictive selection). All three
    # run wire-packed so the dense fallback is the PR 5 packed baseline
    # the delta rungs must beat (the acceptance bar: >=2x lower
    # wire_bytes_per_level on sparse-majority levels). New formats
    # default OFF until these land, matching the pull-gate and wire-pack
    # precedent; every line carries wire_branch_labels +
    # wire_level_counts so the per-branch split is readable next to the
    # byte totals.
    stage "dist-sparse-s20" "$out/dist_sparse_s20.json" \
      TPU_BFS_BENCH_MODE=dist TPU_BFS_BENCH_SCALE=20 \
      TPU_BFS_BENCH_DIST_EXCHANGE=sparse TPU_BFS_BENCH_WIRE_PACK=1
    stage "dist-delta-s20" "$out/dist_delta_s20.json" \
      TPU_BFS_BENCH_MODE=dist TPU_BFS_BENCH_SCALE=20 \
      TPU_BFS_BENCH_DIST_EXCHANGE=sparse TPU_BFS_BENCH_WIRE_PACK=1 \
      TPU_BFS_BENCH_SPARSE_DELTA=1
    stage "dist-sieve-s20" "$out/dist_sieve_s20.json" \
      TPU_BFS_BENCH_MODE=dist TPU_BFS_BENCH_SCALE=20 \
      TPU_BFS_BENCH_DIST_EXCHANGE=sparse TPU_BFS_BENCH_WIRE_PACK=1 \
      TPU_BFS_BENCH_SPARSE_DELTA=1 TPU_BFS_BENCH_SPARSE_SIEVE=1 \
      TPU_BFS_BENCH_SPARSE_PREDICT=1
    # The probe's completion-marker line satisfies got_value, so pstage
    # gives it the same idempotent restart + timeout envelope as the
    # other helper scripts.
    pstage "width-probe" "$out/width_probe.jsonl" scripts/width_probe.py
    pstage "roofline" "$out/roofline.json" scripts/roofline.py
    pstage "parent-scan" "$out/parent_scan.json" scripts/parent_scan_bench.py
    stage "lanes16k-s20" "$out/lanes16k_s20.json" \
      TPU_BFS_BENCH_SCALE=20 TPU_BFS_BENCH_MAX_LANES=16384 \
      TPU_BFS_BENCH_ADAPTIVE=0
    stage "tiled-single" "$out/tiled_single.json" \
      TPU_BFS_BENCH_MODE=single-tiled
    stage "scale22-auto" "$out/scale22.json" TPU_BFS_BENCH_SCALE=22
    stage "flagship-noadaptive" "$out/flagship_noadaptive.json" \
      TPU_BFS_BENCH_ADAPTIVE=0
    stage "width-4096-plain" "$out/flagship_4k_plain.json" \
      TPU_BFS_BENCH_ADAPTIVE=0 TPU_BFS_BENCH_MAX_LANES=4096
    stage "lj-hybrid" "$out/lj_hybrid.json" TPU_BFS_BENCH_MODE=lj-hybrid
    exit 0
  fi
  [ "$i" -lt "$attempts" ] && sleep "${CHIP_SESSION_SLEEP:-300}"
done
echo "chip never came back within the attempt budget"
exit 1
