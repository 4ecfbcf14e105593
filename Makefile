# Build/run harness (the reference's Makefile:1-27 + hack/ scripts, minus
# etcd — the fast path runs on the in-memory control plane).

NATIVE_SO  := minisched_tpu/native/libminisched_native.so

.PHONY: test native chip-smoke start serve benchmark chaos chaos-proc chaos-ha chaos-disk chaos-repl chaos-partition chaos-read chaos-shard chaos-split metrics-smoke docker clean

test: native
	python -m pytest tests/ -q -m 'not slow'

# chaos soak under a FIXED fault-schedule seed: the fabric's injection
# decisions are a pure function of (seed, point, key, ordinal), so a
# failure here reproduces byte-for-byte — override the seed with
# MINISCHED_CHAOS_SEED=<n> to explore other schedules
chaos: native
	MINISCHED_CHAOS_SEED=$${MINISCHED_CHAOS_SEED:-1234} \
		python -m pytest tests/test_chaos_soak.py tests/test_faults.py -q

# the benchmark (BENCHMARK.json, all of it under benchmarks/): checks the
# manifest against the files it is built from and prints the command of
# one run of one cell.  A run ends at a TPU gate anywhere else than on the
# chip (this sandbox: `chiprun -- python3 benchmarks/run.py ...`); the
# driver runs every cell, parent and change, after each PR
benchmark:
	python3 benchmarks/manifest.py
	@echo "one cell, one run (cells: benchmarks/workloads/*.json):"
	@echo "  python3 benchmarks/run.py --workload <cell> --seed <n> --seconds 51 --trace <0|1>"

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

# containerized `make serve` with the WAL on a named volume (the
# reference's docker-compose runs etcd + simulator; see docker-compose.yml)
docker:
	docker compose up --build

clean:
	rm -f $(NATIVE_SO) $(NATIVE_SO).sha256
	find . -name __pycache__ -type d -exec rm -rf {} +
