# Build/run harness (the reference's Makefile:1-27 + hack/ scripts, minus
# etcd — the fast path runs on the in-memory control plane).

NATIVE_SO  := minisched_tpu/native/libminisched_native.so

.PHONY: test native chip-smoke start serve bench bench-wave bench-mesh bench-gang bench-churn bench-wire bench-wal bench-relist bench-repl bench-readscale bench-shard chaos chaos-proc chaos-ha chaos-disk chaos-repl chaos-partition chaos-read chaos-shard chaos-split metrics-smoke docker clean

test: native
	python -m pytest tests/ -q -m 'not slow'

# chaos soak under a FIXED fault-schedule seed: the fabric's injection
# decisions are a pure function of (seed, point, key, ordinal), so a
# failure here reproduces byte-for-byte — override the seed with
# MINISCHED_CHAOS_SEED=<n> to explore other schedules.  Runs with the
# wave PIPELINE explicitly on (its default): fault-injection and the
# overlapped build/evaluate stages must compose — a regression that only
# reproduces serially would otherwise hide behind the kill-switch
chaos: native
	MINISCHED_CHAOS_SEED=$${MINISCHED_CHAOS_SEED:-1234} MINISCHED_PIPELINE=1 \
		python -m pytest tests/test_chaos_soak.py tests/test_faults.py -q

# pipelined-wave micro-bench (CPU): two laps of the live full-roster
# wave engine; FAILS when the loop thread's stall time reaches the build
# time (the pipeline has regressed to serial) or any audit trips
bench-wave: native
	JAX_PLATFORMS=cpu MINISCHED_PIPELINE=1 python bench.py --only wave

# multi-chip live wave engine (ISSUE 7) on an 8-virtual-device CPU mesh:
# the SAME uid-pinned workload through the single-device and the
# mesh-sharded pipelined engine; FAILS on any placement difference, on
# sharded device_total_s >= single-device, on stall >= build (pipeline
# regressed), on any per-wave fallback, on the exactly-once/capacity
# audits, or if XLA's >2s slow-constant-folding alarm fires.  On a real
# multi-chip box drop the XLA_FLAGS forcing to shard over real devices.
bench-mesh: native
	JAX_PLATFORMS=cpu MINISCHED_PIPELINE=1 \
		XLA_FLAGS="$$XLA_FLAGS --xla_force_host_platform_device_count=8" \
		python bench.py --only mesh

# gang churn role (CPU): mixed gang+singleton rounds over a sliced torus
# cluster + a two-gang deadlock probe; FAILS on any stranded partial
# gang, a deadlocked probe, an assume-ledger leak, or node overcommit
bench-gang: native
	JAX_PLATFORMS=cpu MINISCHED_PIPELINE=1 python bench.py --only gang

# sustained-churn serving (ISSUE 8): Poisson arrivals/departures +
# priority-preemption bursts over multi-tenant quota'd namespaces under a
# fixed seed, env-reduced to a tier-1-safe smoke window by default
# (scale up with BENCH_CHURN_WINDOW_S / _NODES / _ARRIVALS_PER_S).  FAILS
# on p99 time-to-bind past BENCH_CHURN_P99_S, a stranded (partial) gang,
# a namespace-quota violation, a quiet tail with zero zero-build waves,
# per-watcher (unshared) fanout encoding, or any standing audit
# (double-bind / node overcommit / assume-ledger leak)
bench-churn: native
	JAX_PLATFORMS=cpu MINISCHED_PIPELINE=1 python bench.py --only churn

# wire-scale watch fanout (ISSUE 9): ≥1000 concurrent REAL HTTP watch
# streams through the selector stream loop with a mutating store behind
# them and deliberately-wedged slow watchers.  FAILS when server thread
# count scales with watcher count (thread-per-watcher regressed), on
# per-watcher (unshared) event encoding, when no slow watcher gets
# evicted, on any missed/duplicated event across an eviction's
# resume/410→relist reconnect, or on p99 delivery latency past
# BENCH_WIRE_P99_S.  Scale with BENCH_WIRE_WATCHERS / _EVENTS_PER_S /
# _WINDOW_S; MINISCHED_STREAMLOOP=0 skips (kill-switch restores the
# thread-per-watcher path)
bench-wire: native
	JAX_PLATFORMS=cpu python bench.py --only wirefan

# group-commit WAL (ISSUE 13): concurrent HTTP writers over fsync=True,
# kill-switch baseline vs pipeline on the same box — fsyncs must
# coalesce and throughput must clear 3x under a real durability barrier
bench-wal: native
	JAX_PLATFORMS=cpu python bench.py --only wal

# replicated control plane (ISSUE 15): one leader + two followers
# tailing the group-commit WAL stream over real HTTP, quorum-ack armed
# at the barrier, versus the MINISCHED_REPL=0 kill-switch on the same
# box.  FAILS on any acked mutation missing from a follower, follower
# WALs diverging from the leader's bytes (fsck --compare), or quorum
# timeouts on a healthy local plane; the record carries the mutate
# p50/p99 replication tax and the storage.quorum_wait_s histogram.
# Phase 3 (ISSUE 16): a FRESH follower attaches while writers run and
# background compaction ships checkpoint generations — FAILS when the
# catch-up blows BENCH_REPL_BOOTSTRAP_S, on any offset-0 re-tail, on a
# deferred compaction, or when the leader's WAL peak exceeds ~2
# compaction intervals of growth (unbounded history)
bench-repl: native
	JAX_PLATFORMS=cpu BENCH_REPL=1 python bench.py --only repl

# relist storm (ISSUE 14): the COW read plane under a thundering herd —
# a SIGKILL-free 410 mass eviction (history-ring compaction) and a
# cold-boot storm of ≥200 simultaneous lists over real HTTP.  FAILS on
# encodes NOT ≪ requests (the memoized list cache regressed), p99 list
# latency past BENCH_RELIST_P99_S, write-path stalls during the storm
# (reads holding the write lock), or any byte difference between the
# MINISCHED_COW_READS=0 locked path and the COW cached/chunked path.
# Scale with BENCH_RELIST_WATCHERS / _OBJECTS
bench-relist: native
	JAX_PLATFORMS=cpu python bench.py --only relist

# follower-serving read plane (ISSUE 17, DESIGN.md §29): 1->3 replica
# list-rate scaling over a real process plane (gated >=1.7x on >=4-core
# boxes; informational where the replicas share one core), encode-once
# list caching verified on EVERY serving replica, and read availability
# across a leader SIGKILL — endpoint-aware min_rv-bounded readers must
# ride the surviving followers through the election (max read gap
# BENCH_READSCALE_GAP_S, zero errors, zero rv regressions).  Scale with
# BENCH_READSCALE_CLIENTS / _PROCS / _OBJECTS / BENCH_READ_FAILOVER_S
bench-readscale: native
	JAX_PLATFORMS=cpu BENCH_READSCALE=1 python bench.py --only readscale

# sharded write plane (ISSUE 18, DESIGN.md §30): the same ≥6-process
# HTTP writer fleet through the shard router against a 1-group and then
# a 2-group plane, every group fsync-armed with a real durability floor
# (MINISCHED_FSYNC_FLOOR_US via BENCH_SHARD_FSYNC_FLOOR_US) — a second
# leader group must BUY write throughput (gated ≥1.5x on ≥4-core boxes;
# informational where the groups share one core, readscale precedent).
# The cross-shard bind batch tax (two-shard commit: two round trips +
# two barriers in parallel) is measured SEPARATELY — it is the price of
# exactly-once across groups, not a regression.  Scale with
# BENCH_SHARD_WRITERS / _WINDOW_S / _BIND_BATCHES
bench-shard: native
	JAX_PLATFORMS=cpu BENCH_SHARD=1 python bench.py --only shard

# process-level chaos: SIGKILL/restart the control-plane child process
# mid-workload (faults/proc.ServerSupervisor) under the same fixed seed.
# Runs BOTH the tier-1 smoke (1 kill) and the slow soak (≥3 scheduled
# kills + checkpoint compaction under fire)
chaos-proc: native
	MINISCHED_CHAOS_SEED=$${MINISCHED_CHAOS_SEED:-1234} \
		python -m pytest tests/test_proc_chaos.py -q

# HA-plane chaos: 3 sharded active-active scheduler engines (separate OS
# processes) over one control plane; engines AND the plane get SIGKILLed
# mid-run (seed-pinned victims).  Runs BOTH the tier-1 smoke (1 engine
# kill) and the slow soak (≥3 process deaths: engine → control plane →
# engine), each ending in the exactly-once / capacity / TTL-rebalance
# audits — mirrors the chaos-proc pattern
chaos-ha: native
	MINISCHED_CHAOS_SEED=$${MINISCHED_CHAOS_SEED:-1234} \
		python -m pytest tests/test_ha_chaos.py -q

# storage-integrity chaos: the disk LIES — CRC-framed WAL bit-flips,
# torn mid-file writes, ENOSPC degraded episodes, checkpoint rot — under
# the same fixed seed.  Runs BOTH the tier-1 smoke (in-process engine,
# ≥5% append faults + one ENOSPC episode + one bit-flip, detection
# asserted by replay AND fsck) and the slow soak (ServerSupervisor
# SIGKILL/restarts with the disk fabric armed inside the child)
chaos-disk: native
	MINISCHED_CHAOS_SEED=$${MINISCHED_CHAOS_SEED:-1234} \
		python -m pytest tests/test_disk_chaos.py -q

# replicated-plane chaos (ISSUE 15): a 3-replica plane (separate OS
# processes, each WAL fsync-armed) under client load; the LEADER gets
# SIGKILLed mid-workload and a follower must win the arbiter-majority
# election within ~2 lease TTLs with ZERO acked-write loss, the deposed
# ex-leader rejoining fenced.  Runs BOTH the tier-1 smoke (in-process
# quorum/fencing/resync paths) and the slow process-level soak — the
# soak ends in the exactly-once bind + WAL-divergence audits
chaos-repl: native
	MINISCHED_CHAOS_SEED=$${MINISCHED_CHAOS_SEED:-1234} \
		python -m pytest tests/test_repl.py tests/test_repl_chaos.py -q

# partition chaos (ISSUE 16, DESIGN.md §28): the network-fault layer
# cuts LINKS instead of processes — the leader is isolated from the
# arbiter majority (data links up) and must fence itself within ~2
# lease TTLs, strictly before a follower wins the election: no
# dual-leader ack window, ever.  Runs BOTH the tier-1 half (NetFabric
# contract + one partition/heal cycle) and the slow soak: writers
# through repeated cycles with background compaction shipping
# checkpoint generations, a dual-leader sampler armed the whole run,
# ending in the zero-acked-loss / replica-consistency (state-replay
# arm) / double-bind audits
chaos-partition: native
	MINISCHED_CHAOS_SEED=$${MINISCHED_CHAOS_SEED:-1234} \
		python -m pytest tests/test_partition_chaos.py -q

# read-plane chaos (ISSUE 17, DESIGN.md §29): the follower-serving read
# plane through leader loss.  Runs BOTH the tier-1 half (every replica
# of a process plane answers rv-bounded reads with the X-Minisched-RV
# watermark, unsatisfiable bounds typed 504, live watch fanout on a
# follower façade, and the interleaved-read property: session-monotonic
# rv + read-your-writes across randomly-chosen replicas under 6-writer
# load) and the slow soak: ≥200 live watch streams spread across three
# replicas while writers run through an arbiter partition AND a leader
# SIGKILL — every stream must resume exactly once (no duplicate rv, no
# gap, no regression) and every watcher must observe every acked create
chaos-read: native
	MINISCHED_CHAOS_SEED=$${MINISCHED_CHAOS_SEED:-1234} \
		python -m pytest tests/test_read_chaos.py -q

# sharded-plane chaos (ISSUE 18, DESIGN.md §30): a 2-group × 3-replica
# plane under cross-shard bind load (every batch spans both groups —
# the two-shard commit path); g0's leader is SIGKILLed mid-run.  Runs
# BOTH the tier-1 smoke (1 kill) and the slow soak (heavier load + a
# second kill on g1), each ending in the standing audits: zero
# acked-write loss, no half-committed cross-shard batch (every retried
# batch fully bound on BOTH sides, full-history double-bind audit over
# all six replica WALs clean), and the unaffected shard never stalls
# (the g1 writer must keep acking THROUGH g0's failover window)
chaos-shard: native
	MINISCHED_CHAOS_SEED=$${MINISCHED_CHAOS_SEED:-1234} \
		python -m pytest tests/test_shard_chaos.py -q

# split-protocol chaos (ISSUE 20, DESIGN.md §31): crash-safe autonomous
# splits on a 2-group × 3-replica plane.  Two kill schedules: the SOURCE
# shard's leader is SIGKILLed mid-handoff (the split must complete after
# failover or abort with a clean thaw), and the split COORDINATOR itself
# is SIGKILLed mid-freeze (every replica's WAL-journaled freeze lease
# must auto-thaw within its TTL — zero stranded frozen namespaces).
# Standing audits both times: zero acked-write loss, exactly-once
# delivery on vector-cursor watches, full-history double-bind audit over
# all replica WALs clean
chaos-split: native
	MINISCHED_CHAOS_SEED=$${MINISCHED_CHAOS_SEED:-1234} \
		python -m pytest tests/test_split_chaos.py -q

# live-telemetry smoke (ISSUE 11): boot the façade + scheduler, drive
# 100 pods to bind, then validate ONLY through the wire — /metrics must
# parse as Prometheus exposition with a non-empty time-to-bind histogram
# covering every bind, /debug/trace must hold complete enqueue→bind span
# chains, and the scrape-side p99 must equal the live registry's
metrics-smoke: native
	JAX_PLATFORMS=cpu python metrics_smoke.py

# native host-table kernels.  The package builds them itself on import
# and records the source digest beside the .so (minisched_tpu/native:
# a library not built from the current native/tablebuilder.cc is rebuilt, never
# loaded) — this target runs that same build and fails if it fell back
native:
	python -c "import sys; from minisched_tpu import native; sys.exit(not native.HAVE_NATIVE)"

# the quickest proof that the served path still starts on the chip: boots
# the stack through __main__.start, drains 10k pods on 5k nodes over HTTP,
# audits the result and the kernels.  One process per chip; exits non-zero
# anywhere JAX finds no TPU (this sandbox: use `chiprun -- python3 chip_smoke.py`)
chip-smoke:
	python chip_smoke.py

# the README scenario on the live engine (the reference's `make start`,
# hack/start_simulator.sh:35 — no etcd/env vars needed here)
start: native
	python -m minisched_tpu.scenario.runner

# standalone process: REST control plane on PORT + PV controller +
# scheduler (sched.go's boot order); see minisched_tpu/__main__.py for
# the optional WAL-store / device-mode / mesh env knobs
serve: native
	PORT=$${PORT:-10251} FRONTEND_URL=$${FRONTEND_URL:-http://localhost:3000} \
		python -m minisched_tpu

bench: native
	python bench.py

# containerized `make serve` with the WAL on a named volume (the
# reference's docker-compose runs etcd + simulator; see docker-compose.yml)
docker:
	docker compose up --build

clean:
	rm -f $(NATIVE_SO) $(NATIVE_SO).sha256
	find . -name __pycache__ -type d -exec rm -rf {} +
