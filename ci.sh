#!/usr/bin/env bash
# CI entry point — the analog of the reference's per-commit pipeline
# (reference: .github/workflows/ci-build.yml:70-103).  Test tiers keep the
# per-commit gate fast while the XLA-compile-bound device tier still runs
# (VERDICT r3 weak #7: an unbudgetable monolithic suite is how red
# artifacts ship unnoticed).
#
#   ./ci.sh            fast tier: every test outside the device tier (<2 min
#                      warm cache) — service, datastore, crypto-oracle,
#                      messages, DP, API, multi-replica, interop.
#   ./ci.sh heavy      device tier: XLA-compile-bound byte-parity suites
#                      (test_prepare, test_ops_*, test_mesh, test_backend,
#                      test_integration_pair).  Always pays cold XLA:CPU
#                      compiles (the persistent cache is deliberately
#                      disabled on CPU - see utils/jax_setup.py).
#   ./ci.sh slow       heavy tier plus RUN_SLOW=1 parametrizations
#                      (full per-family device parity, planar interpret).
#   ./ci.sh all        fast + heavy in sequence.
#   ./ci.sh tier1      the ROADMAP.md tier-1 command VERBATIM, gated on the
#                      recorded DOTS_PASSED floor (tests/tier1_floor.txt):
#                      fewer passing dots than the floor fails the gate.
#   ./ci.sh mxu        MXU field-arithmetic gate: the limb-plane contraction
#                      layer's fuzz/property suite (test_mxu_field.py — exact
#                      vs arbitrary-precision ints for adversarial operands)
#                      plus the prepare byte-parity sweep under BOTH
#                      field_backend values (the -mxu twins in
#                      test_prepare.py) on the virtual-device setup.
#   ./ci.sh mesh       multi-chip gate: the mesh parity matrix (test_mesh.py)
#                      plus the mesh-executor/accumulator suite
#                      (test_mesh_executor.py) on the 8 virtual CPU devices —
#                      sharded mega-batches, per-mesh breaker, sharded
#                      accumulation, flush-tail handling.
#   ./ci.sh chaos      fault-injection gate: tests/test_chaos.py with a FIXED
#                      seed (JANUS_CHAOS_SEED, default 7) — registry/breaker/
#                      budget units plus the 2-replica soak with every
#                      injection point firing at p~=0.2, the mesh-enabled
#                      device-lost run (per-mesh breaker -> oracle fallback,
#                      exactly-once counts), the Poplar1 device-lost case
#                      (ISSUE 10: breaker -> per-report CPU oracle ->
#                      bit-exact heavy-hitter counts with exactly-once
#                      accumulation across the agg-param-keyed journal),
#                      and the fpvec device-lost case (ISSUE 15: the
#                      gradient family degrades to the multi-gadget scalar
#                      oracle and collects exactly once).
#   ./ci.sh poplar     heavy-hitters gate (ISSUE 10 + 13): the jitted AES
#                      kernel (tests/test_aes_jax.py — FIPS-197 vectors,
#                      soft-AES fuzz, the poplar_backend seam), the
#                      executor-routed Poplar1 suite
#                      (tests/test_poplar_executor.py — multi-request walk
#                      parity, level-keyed bucket identity,
#                      breaker/backpressure parity, device-resident sketch
#                      refs + dead-ref oracle replay, the walk/sketch
#                      double buffer, the 2-job x 2-level e2e,
#                      deferred-journal crash replay) plus the
#                      protocol/batch suites (test_poplar1.py,
#                      test_poplar1_batch.py).
#   ./ci.sh chaos crash  process-level crash stage: the SIGKILL/restart soak
#                      (tests/test_crash_chaos.py, slow-marked so tier-1
#                      timing is unaffected) — real replica binaries killed
#                      mid-step, lease reaper + journal replay verified —
#                      plus the collection-replica SIGKILL-mid-journal-replay
#                      case (ISSUE 11: orphaned rows replayed exactly once by
#                      a clean replacement binary, replay-consumed metric
#                      delta == orphan count, results unchanged).
#   ./ci.sh chaos partition  network-partition stage (ISSUE 11 + 13): the
#                      asymmetric leader->helper blackhole soak (jobs quiesce
#                      with retryable jittered backoff — zero attempt-budget
#                      abandonments, zero breaker trips, zero expired leases
#                      — then heal -> exactly-once counts, zero SLO false
#                      breaches), the FLAPPING-LINK soak (deterministic
#                      on/off schedule, mid-exchange resets, suspect-dwell
#                      restarts under churn, exactly-once after settle),
#                      plus the peer-health / deadline-budget / Retry-After
#                      unit suite (tests/test_peer_health.py).
#   ./ci.sh chaos brownout  datastore-brownout stage (ISSUE 17): the
#                      2-replica fleet soak with every datastore.tx.begin
#                      blackholed/erroring for a bounded window — health
#                      tracker SUSPECT, upload front door shedding 503
#                      before HPKE work, both routers serving their FROZEN
#                      ownership view (zero migrations, zero abandons,
#                      zero breaker trips, suppression counted on
#                      /metrics), heal -> exactly-once collection with
#                      exact sums — plus the real-death-after-brownout
#                      case (a replica dead past the thaw-confirmation TTL
#                      still loses its tasks) and the db-health unit suite
#                      (tests/test_db_health.py: classification tables,
#                      seeded backoff, tx deadlines, freeze/thaw).
#   ./ci.sh chaos poison  blast-radius stage (ISSUE 19): the poisoned-batch
#                      soak on the journaled fleet — marked-poison uploads
#                      failing the vectorized HPKE open, poison report rows
#                      failing the executor's prep staging, and a mid-soak
#                      bit-flip/truncation wave over stored journal rows —
#                      every poison row lands in quarantined_reports (batch
#                      bisection isolates offenders in O(log B) passes,
#                      journal CRC32C fences catch the corrupt rows), zero
#                      global breaker trips, exactly-once exact-sum
#                      collection of the healthy cohort; plus the
#                      bisection/CRC/quarantine unit suite
#                      (tests/test_quarantine.py) and the poison-free
#                      parity fence (stored rows and prepare messages
#                      bit-identical with the machinery armed).
#   ./ci.sh fpvec      gradient-aggregation gate (ISSUE 15): the
#                      multi-gadget device FLP plane — fpvec device-vs-
#                      oracle bit-exact fuzz (vpu + mxu, leader + helper,
#                      canonical-padded mixed batches, adversarial
#                      broken-bit and norm-violating reports), the e2e
#                      gradient scenario (task API -> real drivers ->
#                      executor coalescing -> ZCdpDiscreteGaussian
#                      collect), and the dispatch-classification suite
#                      (tests/test_backend_fallback.py).  XLA-compile
#                      bound (~15-30 min on CPU).
#   ./ci.sh coldstart  shape-churn gate (ISSUE 8): pow2 canonicalization
#                      oracle-parity sweep (tests/test_shape_canonical.py,
#                      incl. the RUN_SLOW matrix: all circuit families x
#                      both agg sides x both field layouts) + the
#                      background-warmup / compile-cache suite
#                      (tests/test_warmup.py).
#   ./ci.sh obs        observability gate: tests/test_observability.py +
#                      tests/test_slo.py + tests/test_cost_attribution.py —
#                      trace-context propagation (incl. upload-minted traces
#                      + linked-trace --stats), the metrics fallback, the
#                      OTLP exporter's first-class no-op path, SLO burn-rate
#                      math against hand-computed fixtures, the health
#                      server's zpages (/statusz included), per-task
#                      device-seconds attribution (conservation proven for
#                      multi-task / oracle-fallback / padded-tail flushes),
#                      the executor flight recorder (ring bound, breaker-trip
#                      + slow-flush dumps), the cost_report tool,
#                      the jax-profiler-server wiring, the metric
#                      help-text audit, and the golden metric-name/label
#                      manifest (tests/metric_manifest.txt) that catches
#                      silent metric renames.
#   ./ci.sh load       upload front-door gate (ISSUE 14): the SLO-judged
#                      load soak — tools/loadgen.py drives real HTTP
#                      uploads against a leader+helper+creator+driver
#                      fleet of _BOOT binaries at a host-scaled target
#                      rate (breach-free upload_to_commit/commit_age burn
#                      rates, zero sheds), then past the shed threshold
#                      (a queue-starved leader replica with a wedged open
#                      stage: 503 + Retry-After, janus_upload_shed_total
#                      moving, admitted reports' SLOs still green), then
#                      exactly-once collection of every admitted report
#                      and a complete upload->commit->flush->collection
#                      merged-trace critical path.  `./ci.sh load fast`
#                      runs only the scaled-down in-process smoke plus
#                      the front-door unit suite (batched-open parity,
#                      shed paths, flush-race regression).
#   ./ci.sh ingest     zero-copy ingest gate (ISSUE 18): the write-behind
#                      report-journal unit/e2e suite (tests/test_ingest.py —
#                      journaled-vs-synchronous byte parity, ACK-before-
#                      materialize durability, replay idempotence, the
#                      direct-staging handoff, GC/journal coexistence,
#                      wedged-writer sheds, the loadgen first-prepare
#                      percentile math) plus the binary-level journaled
#                      crash case (SIGKILL between ACK and materialization
#                      with GC running -> replay exactly once, duplicate
#                      re-uploads absorbed, decoy proves GC live).
#   ./ci.sh fleet      fleet control plane gate (ISSUE 16): rendezvous
#                      routing units, fleet_members row plumbing,
#                      ownership-filtered acquisition, migration behind the
#                      takeover grace, the fleet-shared suspect set, the
#                      in-process 2-JobDriver exactly-once case, and (via
#                      RUN_SLOW) the binary-level acceptance case — two
#                      aggregation_job_driver binaries with fleet.enabled,
#                      disjoint ownership + per-replica compile isolation
#                      on /statusz, SIGKILL-driven migration within the
#                      heartbeat TTL, exactly-once collection.
#   ./ci.sh dryrun     the driver's gates: multichip dryrun + entry compile.
set -euo pipefail
cd "$(dirname "$0")"

tier="${1:-fast}"
case "$tier" in
  fast)
    exec python -m pytest tests/ -q -m "not device"
    ;;
  heavy)
    exec python -m pytest tests/ -q -m device
    ;;
  slow)
    # RUN_SLOW covers every slow-marked test, device-tier or not.
    RUN_SLOW=1 exec python -m pytest tests/ -q -m "device or slow"
    ;;
  all)
    python -m pytest tests/ -q -m "not device"
    exec python -m pytest tests/ -q -m device
    ;;
  postgres)
    # Live-Postgres tier (VERDICT r4 missing #1): provision a throwaway
    # server when pg binaries exist, else honor a caller-supplied DSN
    # (JANUS_TPU_TEST_PG_DSN).  Runs the live datastore suite — including
    # the fleet control plane's contended cases (ISSUE 16 satellite:
    # member-registration insert race, ownership-filtered acquisition
    # under real MVCC contention, stale-heartbeat migration) — plus the
    # dialect guards.
    if [ -z "${JANUS_TPU_TEST_PG_DSN:-}" ]; then
      if command -v initdb >/dev/null && command -v pg_ctl >/dev/null; then
        PGDIR="$(mktemp -d /tmp/janus-pg.XXXXXX)"
        # trap FIRST: a failure in any provisioning step below must not
        # leak a running server or the temp dir (set -e exits immediately)
        trap 'pg_ctl -D "$PGDIR/data" -m immediate stop >/dev/null 2>&1; rm -rf "$PGDIR"' EXIT
        initdb -D "$PGDIR/data" -U postgres >/dev/null
        pg_ctl -D "$PGDIR/data" -o "-k $PGDIR -p 54329 -c listen_addresses=''" -w start >/dev/null
        createdb -h "$PGDIR" -p 54329 -U postgres janus_test
        export JANUS_TPU_TEST_PG_DSN="postgresql://postgres@/janus_test?host=$PGDIR&port=54329"
      else
        echo "no Postgres server available: install postgres binaries or set JANUS_TPU_TEST_PG_DSN" >&2
        exit 3
      fi
    fi
    exec python -m pytest tests/test_postgres_live.py \
      "tests/test_multi_replica.py::TestSqlDialectGuards" -q
    ;;
  tier1)
    # Regression gate against the seed baseline: run the tier-1 command
    # exactly as ROADMAP.md records it (single source of truth — edits to
    # the roadmap automatically propagate here), then compare the passing
    # dot count to the recorded floor.  The suite can hit its own timeout
    # (rc=124 at the seed), so the gate is the DOTS_PASSED floor, not rc.
    cmd=$(sed -n 's/^\*\*Tier-1 verify:\*\* `\(.*\)`$/\1/p' ROADMAP.md)
    if [ -z "$cmd" ]; then
      echo "tier-1 command not found in ROADMAP.md" >&2
      exit 2
    fi
    floor=$(cat tests/tier1_floor.txt)
    set +e
    bash -c "$cmd" 2>&1 | tee /tmp/_t1_gate.log
    rc=${PIPESTATUS[0]}
    set -e
    # the command itself emits the canonical count; parse, don't recompute.
    # Match anywhere in the line: when the timeout kills pytest mid-line,
    # the marker is appended to a partial dots line (no leading newline),
    # and an anchored match would read a passing run as 0.
    dots=$(grep -ao 'DOTS_PASSED=[0-9]*' /tmp/_t1_gate.log | tail -n1 | cut -d= -f2)
    dots=${dots:-0}
    echo "tier1: DOTS_PASSED=$dots floor=$floor rc=$rc"
    if [ "$dots" -lt "$floor" ]; then
      echo "tier1 REGRESSION: DOTS_PASSED=$dots < floor=$floor" >&2
      exit 1
    fi
    exit 0
    ;;
  chaos)
    # Fixed seed so the per-point fault decision sequences replay run to
    # run; override JANUS_CHAOS_SEED to explore other schedules.  The
    # accumulator suite rides along: the soak now runs with the
    # device-resident store enabled (spill/evict faults firing) and
    # test_accumulator.py covers the store/scheduler/replay units.
    export JANUS_CHAOS_SEED="${JANUS_CHAOS_SEED:-7}"
    if [ "${2:-}" = "crash" ]; then
      # Process-level crash stage (ISSUE 4 + 11): SIGKILL/restart soak over
      # real replica binaries, the lease-holder-death redelivery test, and
      # the collection-replica SIGKILL-mid-journal-replay case.
      # Slow-marked (RUN_SLOW gates it) so the tier-1 budget is
      # unaffected; needs `cryptography` (the tests skip without it).
      RUN_SLOW=1 exec python -m pytest tests/test_crash_chaos.py -q
    fi
    if [ "${2:-}" = "partition" ]; then
      # Network-partition stage (ISSUE 11 + 13): the asymmetric blackhole
      # soak, the FLAPPING-LINK soak (half-open probes interleaved with
      # mid-exchange resets, suspect-dwell restart under churn), and the
      # peer-health/retry units.  Slow-marked — RUN_SLOW gates them.
      RUN_SLOW=1 exec python -m pytest \
        "tests/test_chaos.py::test_partition_soak_asymmetric_heal_exactly_once" \
        "tests/test_chaos.py::test_partition_flap_soak_suspect_dwell_restart_exactly_once" \
        tests/test_peer_health.py -q
    fi
    if [ "${2:-}" = "brownout" ]; then
      # Datastore-brownout stage (ISSUE 17): the migration-storm
      # suppression soak + the real-death-after-brownout takeover case,
      # plus the db-health unit suite (classification, backoff, deadlines,
      # freeze/thaw).
      exec python -m pytest tests/test_brownout_chaos.py tests/test_db_health.py -q
    fi
    if [ "${2:-}" = "poison" ]; then
      # Blast-radius stage (ISSUE 19): poisoned-batch bisection quarantine
      # + corruption-tolerant journal replay.  The soak plus the
      # bisection-harness/CRC32C/quarantine-ledger unit suite.
      exec python -m pytest tests/test_poison_chaos.py tests/test_quarantine.py -q
    fi
    exec python -m pytest tests/test_chaos.py tests/test_brownout_chaos.py tests/test_poison_chaos.py tests/test_quarantine.py tests/test_db_health.py tests/test_peer_health.py tests/test_accumulator.py tests/test_crash_chaos.py tests/test_canary.py -q -m "not slow"
    ;;
  canary)
    # Canary plane gate (ISSUE 20): the black-box prober's verdict state
    # machine, degradation-aware backoff (db-SUSPECT + shed escalation),
    # the corrupt-aggregate fence and blackout chaos case against a real
    # in-process pair, and the trace-percentile extractor units.
    exec python -m pytest tests/test_canary.py tests/test_trace_percentiles.py -q -m "not slow"
    ;;
  mesh)
    # Multi-chip gate (ISSUE 6).  test_mesh.py is device-tier (sharded
    # XLA compiles); test_mesh_executor.py also rides the fast tier — this
    # stage runs both together for a focused mesh signal.
    exec python -m pytest tests/test_mesh.py tests/test_mesh_executor.py -q
    ;;
  poplar)
    # Heavy-hitters gate (ISSUE 10 + 13): Poplar1 through the executor's
    # agg-param-keyed dispatch plane, the jitted AES walk (FIPS-197
    # vectors + soft-AES fuzz, tests/test_aes_jax.py), and the
    # device-resident sketch path (ResidentRefs across the ping-pong
    # persistence hop, deferred drains, dead-ref oracle replay, the
    # walk/sketch double buffer).  The soft-AES fallback
    # (utils/softaes.py) keeps the IDPF walk runnable without the
    # `cryptography` package; the e2e/replay cases still need it (or the
    # shim) for datastore column encryption and skip cleanly otherwise.
    exec python -m pytest tests/test_aes_jax.py tests/test_poplar_executor.py \
      tests/test_poplar1.py tests/test_poplar1_batch.py -q
    ;;
  mxu)
    # MXU field-arithmetic gate (ISSUE 7): dot_general contraction layer
    # exactness (random + adversarial operands, both fields, matvec/matmul
    # shapes, chunked long-K, batched inversion, compiled-HLO dot evidence)
    # + the full prepare byte-parity matrix under field_backend vpu AND mxu.
    exec python -m pytest tests/test_mxu_field.py \
      "tests/test_prepare.py::test_device_prepare_matches_oracle" -q
    ;;
  coldstart)
    # Shape-churn gate (ISSUE 8): canonicalization parity is asserted,
    # never assumed — the full sweep (slow-marked cases included) plus
    # the warmup/compile-cache machinery.
    RUN_SLOW=1 exec python -m pytest tests/test_shape_canonical.py tests/test_warmup.py -q
    ;;
  fpvec)
    # Gradient-aggregation gate (ISSUE 15): the multi-gadget device FLP
    # plane, bit-exactness asserted never assumed — fuzz (both field
    # layouts, both sides, canonical-padded mixed batches, adversarial
    # reports), the e2e gradient scenario with real DP noise, and the
    # routing/classification suite.
    RUN_SLOW=1 exec python -m pytest tests/test_fpvec_device.py \
      tests/test_backend_fallback.py -q
    ;;
  obs)
    # Observability gate (ISSUE 5 + 9): runs everywhere — the pure-Python
    # metrics fallback keeps the metric assertions meaningful even where
    # prometheus_client is absent, the OTLP suite PROVES the exporter
    # inert where the opentelemetry-sdk is absent, and the SLO suite
    # checks burn-rate math against hand-computed histogram fixtures;
    # datastore-backed cases skip without `cryptography`.
    exec python -m pytest tests/test_observability.py tests/test_slo.py \
      tests/test_cost_attribution.py -q
    ;;
  load)
    # Upload front-door gate (ISSUE 14).  The full stage spawns a real
    # binary fleet and sustains minutes of traffic (slow-marked); the
    # fast variant is the in-process smoke + the unit suite.
    if [ "${2:-}" = "fast" ]; then
      exec python -m pytest tests/test_upload_frontdoor.py \
        "tests/test_load_soak.py::test_loadgen_fast_smoke" -q
    fi
    RUN_SLOW=1 exec python -m pytest tests/test_load_soak.py \
      tests/test_upload_frontdoor.py -q
    ;;
  ingest)
    # Zero-copy ingest gate (ISSUE 18).  The fast suite runs everywhere;
    # the journaled SIGKILL-mid-flush crash case spawns real binaries and
    # is slow-marked, so RUN_SLOW pulls it in here without touching the
    # tier-1 budget.
    python -m pytest tests/test_ingest.py -q
    RUN_SLOW=1 exec python -m pytest tests/test_crash_chaos.py -q \
      -k journaled_ingest
    ;;
  fleet)
    # Fleet control plane gate (ISSUE 16).  RUN_SLOW pulls in the
    # binary-level SIGKILL-migration acceptance case (~3 min: two driver
    # binaries + a helper binary on CPU-pinned jax).
    RUN_SLOW=1 exec python -m pytest tests/test_fleet.py -q
    ;;
  dryrun)
    python __graft_entry__.py 8
    exec python - <<'EOF'
import jax
import __graft_entry__ as g
fn, args = g.entry()
jax.jit(fn).lower(*args).compile()
print("entry() compile ok")
EOF
    ;;
  *)
    echo "usage: ./ci.sh [fast|heavy|slow|all|tier1|mxu|mesh|poplar|chaos|chaos crash|chaos partition|chaos brownout|chaos poison|canary|coldstart|fpvec|obs|load|load fast|ingest|fleet|postgres|dryrun]" >&2
    exit 2
    ;;
esac
