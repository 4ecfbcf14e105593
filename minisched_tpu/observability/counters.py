"""Named monotonic counters: the recovery-side ledger of the fault story.

The fault fabric (minisched_tpu.faults) counts what was INJECTED; these
counters record what the system DID about it — remote retries, informer
reconnects, assume-lease expiries, failed bind batches.  A chaos soak
asserts both sides: faults fired, and every recovery path that should
have answered them actually ran.

One process-global registry (``GLOBAL``) keeps call sites one-liners —
``counters.inc("remote.retry")`` — without threading a handle through
every constructor; tests snapshot/reset around their scenario.

The HA plane (minisched_tpu.ha) records its lifecycle here under the
``ha.`` prefix — surfaced in the bench ``ha`` role's record:

    ha.lease_acquire / ha.lease_takeover / ha.lease_renew
        — member-lease CAS outcomes (takeover = an expired lease stolen)
    ha.lease_lost / ha.lease_expired / ha.lease_release / ha.lease_gc
        — a renewal losing its CAS; a peer observed dead by TTL; a
          graceful departure; long-dead lease reaping
    ha.member_join / ha.member_lost / ha.epoch_bump
        — membership-view changes (each member counts its OWN view, so N
          survivors observing one death add N to member_lost)
    ha.shard_adopt / ha.shard_adopt_pods
        — failover rebalances and how many orphaned pending pods the
          adopting engine re-admitted

The pipelined wave engine (engine/pipeline.py) records under
``wave_pipeline.``; its TIMERS (stall, build) live in the engine's
CycleMetrics, not here — counters are integers:

    wave_pipeline.waves
        — waves evaluated through the pipelined (overlapped) path
    wave_pipeline.build_fallback
        — batches the build worker handed back to the serial wave path
          (encode overflow, empty roster, priority bypass, build fault)
    wave_pipeline.rearb_requeued
        — pipelined winners rejected by commit-time re-arbitration
          (capacity taken by the overlapped previous wave) and requeued
    wave_pipeline.dirty_rows
        — node aggregate rows re-encoded incrementally (vs a full
          O(all nodes) fill per wave); the bench divides by waves
    wave_pipeline.zero_build_waves
        — pipelined waves whose node-table build was skipped WHOLESALE
          by the idle-wave gate (below); the churn bench's
          zero-build-wave ratio divides this by wave_pipeline.waves

The sustained-churn layer (ISSUE 8, DESIGN.md §22) records the
cheap-when-quiet story — surfaced in the bench ``churn`` role's record:

    wave_build.skipped
        — CachedNodeTableBuilder builds answered from the idle-wave
          reuse cache: empty dirty-set, unchanged cache epoch (or
          (name, rv) signature), same capacities, byte-equal
          assume-delta fingerprint → the previous tables returned
          wholesale, zero encode/fold/pack/transfer.  Counted at the
          builder, so serial and pipelined waves both land here.
    watch.fanout.encoded / watch.fanout.shared
        — HTTP watch streams serializing an event: first encode of the
          framed wire chunk (memoized on the WatchEvent the store fans
          out) vs. reuses by every other stream.  encoded staying
          O(events) while shared grows O(events × watchers) IS the
          shared-payload claim; the churn fanout microbench gates on it.
    watch.fanout.evicted_slow
        — watchers evicted because their queue exceeded the per-watch
          bound (DEFAULT_WATCH_QUEUE_EVENTS): the stream dies like a
          drop and the consumer recovers via resume/410→relist —
          degrade-the-laggard, never block-the-store-lock.
    watch.disconnects
        — watch streams whose client hung up mid-chunk (previously a
          silent exit); the handler prunes the registration immediately.
    queue.quota_held / queue.quota_admitted / queue.quota_gang_bypass
        — namespace-quota admission at the scheduling queue: arrivals
          parked in the per-namespace hold FIFO, holds promoted into
          freed slots (FIFO, deferred past a pop_batch so a tenant's
          share of one wave stays at its cap), and gang members
          admitted past the cap (an all-or-nothing gang is never split
          across the quota boundary).
    queue.quota_violation
        — tripwire, not a code path: a non-gang NEW arrival admitted
          past its namespace cap (requeues and gang bypass may exceed
          by contract; this may not).  Any nonzero value is an
          accounting bug; the churn bench fails on it.

The wire layer (ISSUE 9: the selector stream fanout in
controlplane/streamloop and the pooled keep-alive client in
controlplane/httppool) records under ``wire.`` — surfaced in the wire
bench records (``scheduler_over_http`` + ``wire_fanout``) alongside the
``watch.fanout.*`` family above:

    wire.streams_adopted
        — watch streams DETACHED from their handler thread into the
          selector loop after handshake + snapshot/resume replay (the
          thread returns to the pool: N watchers cost N sockets + ONE
          thread; MINISCHED_STREAMLOOP=0 keeps this at zero)
    wire.streams_active
        — gauge: streams the loop currently owns
    wire.evicted_outbuf
        — streams evicted because their per-socket out-buffer exceeded
          its byte bound: the SOCKET-level laggard (the kernel refused
          the bytes), distinct from the store-queue eviction counted by
          watch.fanout.evicted_slow.  Both die like a dropped stream
          and the consumer recovers via resume/410→relist; the wire
          bench requires the recovery to be exactly-once.
    wire.partial_writes
        — non-blocking sends the kernel truncated (backpressure
          evidence: the loop parked the remainder in the out-buffer)
    wire.keepalives
        — idle keepalive chunks written by the loop (same 0.5s cadence
          and bytes as the thread path)
    wire.pool_open / wire.pool_reuse
        — keep-alive client connections freshly opened vs checked out
          warm (reuse ≫ open is the pooled-transport claim; every
          RemoteStore/HTTPClient request rides one of these)
    wire.pool_stale_retry
        — requests replayed ONCE on a fresh connection after a REUSED
          socket turned out dead (the server closed it while idle —
          keep-alive timeout, injected http.500, restart); internal to
          the pool, never consumes the caller's backoff budget
    wire.relist_requests / wire.relist_bytes_shared
        — LIST verbs served by the REST façade, and the payload bytes
          answered from the COW read plane's memoized list cache
          (shared bytes streamed chunked, not re-encoded; ISSUE 14):
          bytes_shared / requests ≈ mean list size once the cache is
          warm, and the relist bench gates encodes ≪ requests
    store.list_cache.encodes / store.list_cache.hits
        — memoized list-payload cache outcomes keyed
          (kind, namespace, rv)-via-snapshot: a relist storm of N
          informers at one rv costs ONE encode (plus benign
          double-encode races) and N−1 hits; every snapshot swap
          invalidates wholesale by replacing the cache's owner

The object codec (controlplane/checkpoint: every REST body, watch line,
checkpoint and WAL record is decoded through a plan kept per type):

    decode.plans_built
        — dataclass and container types (``Pod``, ``List[Container]``,
          ``Dict[str, str]``, …) whose decode plan was derived from
          their annotations: some dozens at import, for every REST
          kind, and one more a type first met later.  It must stand
          still while the process serves: a count that moves with the
          objects decoded means ``get_type_hints`` is back on the
          per-object path (PERF.md §6, PR 27)

The blocked scan lane (engine/scan_groups + ops/sequential) says how
full its blocks are, once a grouping (a flush of the scan backlog, and
each retry round of it):

    scan.rows_live / scan.rows_total / scan.rows_narrow
        — pods handed to the blocked kernel, the rows they were laid
          out in (``None`` padding included: SCAN_BLOCK_SIZE a block in
          the wide layout, SCAN_NARROW_WIDTH a pod in the narrow one),
          and how many of the pods went down the narrow layout (the
          grouping's trailing blocks of exactly one live pod).
          live ÷ total is the fill: near 1 where groups are many and
          small (wide, full blocks) and where every pod shares one
          interaction group (narrow took them all: narrow = live);
          1 ÷ 32 would be a one-group backlog that the narrow layout
          missed (benchmark metrics ``scan.fill_share``,
          ``scan.narrow_share``)
    scan.calls_wide / scan.calls_narrow
        — kernel calls of the blocked lane by the layout of their rows
          (one grouping is cut into the calls its fill asks for: the
          head wide, the trailing one-pod blocks narrow)
    scan.combos_live / scan.combos_total
        — once a constraint build of the scan lanes (blocked and
          exact): the (namespaces, selector, topology key) combos among
          the build's pods, and the rows the combo axis was padded to
          (models/constraints.cap_tier: 32, 256, 2048, ...); live ÷
          total is how full the combo planes a step reads are
          (benchmark metric ``scan.combo_fill_share``)
    scan.excl_terms / scan.excl_nodes / scan.excl_capacity
        — once a constraint build of the scan lanes: the distinct
          reverse required anti-affinity terms of the pods that are
          placed (a combo row each, whatever the cluster holds: 1 where
          every pod carries the one term), the nodes their owners'
          domains ban (true cells of ``combo_excl``), and terms × real
          nodes; nodes ÷ capacity is how much of the cluster the reverse
          direction bans (benchmark metric ``scan.excl_nodes_share``)
    constraint_index.pods_removed
        — assigned pods the constraint index dropped on a DELETED event
          (models/constraint_index: their counts, owner values and
          volume mounts go with them)

The device engine counts the pods each of its programs placed, as they
are handed to the commit (engine/device_scheduler ``_commit_winners``):

    sched.lane_pods.wave / .wide / .narrow / .exact
        — the packed repair wave, the blocked scan's wide (32 rows a
          step) and narrow (a pod a step) layouts, the exact per-pod
          scan
    sched.evaluated_pods / sched.unschedulable_pods
        — once an evaluate of any of those lanes: the pods it was handed,
          and those it returned without a node (benchmark metric
          ``queue.unschedulable_share``: 0 while a cluster has room).
          All of these and the ``scan.*`` counters above are registered
          at 0 when a device engine is constructed

The device engine says what it runs on and when a device call fails
(ISSUE 21: no fallback may hide the device) — asserted by chip_smoke.py:

    engine.device.<platform>.<device_kind>  (gauge)
        — JAX devices visible to a device-mode engine booted through
          ``__main__.start``, named after the first one
          (``engine.device.tpu.TPU_v5_lite 1`` on one v5e chip;
          ``engine.device.cpu.cpu N`` means the "TPU wave engine" is
          running on XLA:CPU)
    engine.mesh_devices  (gauge)
        — devices the engine's wave mesh spans; 0 = single-device
    wave.parked / wave.parked.<cause>
        — batches (repair waves and scan-lane chunks/flushes) whose
          device call RAISED and whose pods went back through
          error_func, in total and per exception type name.  A
          transient fault adds a few; a program the compiler refuses
          adds one per retry for ever — the requeue keeps the process
          up and /healthz green, so this counter (plus one stderr line
          per park and the ``wave_park`` trace span) is the only sign
    wave.dispatch_healed
        — packed-program dispatches that hit jax's wrong-arity
          executable fault ("supplied N buffers but compiled program
          expected M") and were recompiled once (models/tables.
          PackedCaller); one stderr line each.  Repeats are a bug

The multi-chip live wave engine (ISSUE 7: DeviceScheduler over a
jax.sharding.Mesh, parallel/sharding.MeshPackedCaller) records under
``wave_mesh.`` — surfaced in the bench ``mesh`` child and the c5
``wave_breakdown`` block:

    wave_mesh.pod_shards / wave_mesh.node_shards
        — the mesh factoring the engine acquired at startup (set once
          per engine construction; 2×4 on an 8-device host)
    wave_mesh.waves
        — repair waves evaluated SHARDED over the mesh (the tentpole
          path; a mesh engine whose count stays 0 is running degraded)
    wave_mesh.fallbacks
        — waves re-dispatched on ONE device after a sharded-evaluate
          failure (the per-wave fallback ladder; later waves retry the
          mesh — repeated fallbacks mean the mesh is effectively dead)
    wave_mesh.pad_pod_rows / wave_mesh.pad_node_rows
        — table rows shipped beyond the live wave/roster (mesh-axis
          capacity alignment waste); the bench divides by waves

The durable layer (controlplane/durable + walio + fsck) records the
storage-integrity story under ``storage.`` — surfaced in the bench
``disk`` role's record:

    storage.degraded_enter / storage.degraded_recovered
        — ENOSPC/EIO latched the store read-only; a recovery probe
          re-armed writes (dwell time lives in
          DurableObjectStore.storage_stats, not here)
    storage.append_error / storage.recovery_probe
        — WAL appends that failed at the OS; probe attempts while
          degraded (each consults the disk.enospc schedule, so an
          injected episode has real dwell)
    storage.degraded_parks
        — engine waves/binds parked on a typed StorageDegraded instead
          of crashing (capacity released with the requeue)
    storage.remote_degraded_retry
        — HTTP 507 answers the remote client retried with backoff
    storage.event_dropped_degraded
        — volatile Events shed while the disk was full (best-effort)
    events.written / events.trimmed / events.batches
        — the events broadcaster's writer thread (controlplane/client
          .EventRecorder): Event objects created, Event objects deleted
          as the cap was passed, and batches landed (one ``delete_many``
          + one ``create_many`` each, whatever the batch holds).
          written ÷ batches is how many decisions share one store
          transaction: 1 in a scenario, a score while the writer keeps
          pace with a wave's commit, hundreds when it falls behind.
          Registered at 0 when a recorder is given a store.
    storage.wal_corrupt_detected / storage.wal_salvaged
        — replay found a bad frame (bit-flip / torn mid-file write);
          salvage truncated at it because the checkpoint covered the
          loss (refusals re-raise the typed WalCorrupt instead)
    storage.ckpt_digest_mismatch / storage.ckpt_unverified
        — sha256 sidecar convicted a checkpoint; a pre-integrity
          checkpoint restored without a sidecar
    storage.ckpt_fallback_prev / storage.ckpt_fallback_replay
        — the restore chain fell back to the previous generation / to
          full WAL+archive replay
    storage.scrub_runs / storage.scrub_findings
        — background integrity passes and what they found
    storage.bitflip_injected / storage.torn_injected /
    storage.ckpt_corrupt_injected
        — the fault fabric's lying-disk evidence (what was WRITTEN
          corrupt; the detection counters above are the other half)
    storage.group_commit.groups / storage.group_commit.records
        — commit-barrier turns the group-commit pipeline ran, and the
          mutations they carried; records/groups is the live
          coalescing ratio (1.0 = no concurrency, nothing batched)
    storage.group_commit.fsyncs_saved
        — fsyncs the barrier avoided versus the per-mutation path
          (len(group)−1 per fsync-armed group): the entire point of
          group commit, and the bench `wal` role's headline gate

The robustness layer (PR 1: retry.py, informer reconnects, assume
leases) records the recovery evidence the chaos soaks assert on:

    remote.retry / remote.conflict_retry
        — remote-store requests replayed after a transient transport
          error / after a CAS Conflict the caller asked to retry
    remote.bind_retry_dedup / remote.bind_ack_replayed
        — AlreadyBound-to-our-node answers converted to success after a
          retransmission (the first attempt committed before its socket
          died); the HTTPClient facade's mirror of the same dedup
    informer.reconnect / informer.resume / informer.relist_on_410 /
    informer.open_retry
        — watch streams re-opened after a drop, resumed from the last
          seen rv, relisted after the history floor answered 410, and
          initial opens retried at boot instead of crashing the service
    informer.relist_jitter_s
        — jitter SLEEPS taken before a 410-triggered relist (a count,
          not seconds: each is a fabric-deterministic draw from
          [0, MINISCHED_RELIST_JITTER_S)) — the spread that keeps a
          mass eviction from relisting on one tick
    assume.lease_confirmed / assume.lease_expired /
    assume.lease_renewed_bound / assume.lease_renewed_unreachable /
    assume.lease_requeued / assume.lease_probe_deferred /
    assume.revalidate_on_reconnect
        — assume-lease lifecycle: confirmations by observed bind,
          TTL expiries, renewals for already-bound pods, renewals
          granted while the plane was unreachable (never expire on a
          blind spot), capacity released + pod requeued on a lost bind,
          probes deferred while the plane was unreachable, and
          post-reconnect revalidation
    engine.bind_batch_failed
        — bind transactions that failed per-item instead of stranding
          their wave

TIMERS live next door: observability/hist.py holds the live latency
histograms (time-to-bind, wave phases, HTTP request latency, watch
delivery lag, WAL append/fsync) under the same global-registry
convention, rendered together with these counters by ``/metrics``.

The gang subsystem (plugins/coscheduling + engine/gang) records under
``gang.`` — surfaced in the bench ``gang`` role's record:

    gang.admitted
        — gangs whose members ALL held assume leases and were allowed
          through Permit together (the all-or-nothing invariant)
    gang.ttl_expired
        — gang TTLs that fired on a partial gang (every waiting member
          rejected, their assumes released)
    gang.ttl_requeued
        — members a TTL release sent back through the ACTIVE queue
          (prompt retry; no cluster event would wake them from the
          unschedulableQ)
    gang.rearb_atomic_release
        — pipelined gang members released WITH a sibling that lost
          commit-time re-arbitration (a gang is kept or released whole)
    gang.preempt_shielded
        — lower-priority gang-member pods DefaultPreemption excluded
          from a victim search (gang capacity is unpreemptable until
          whole-gang eviction lands — evicting one member would strand
          the rest as a partial gang; the churn bench audits this)

The replicated control plane (controlplane/repl + the quorum hook in
durable.py — DESIGN.md §27) records under ``storage.repl.`` — the
chaos-repl soak's replication evidence:

    storage.repl.groups / storage.repl.bytes
        — commit groups (and their WAL bytes) the leader registered
          with the replication hub at the group-commit barrier: the
          unit of shipping, acking, and digest gossip
    storage.repl.acks
        — follower durability acks the leader recorded (each a
          max-monotonic "my WAL is fsynced through offset N")
    storage.repl.quorum_timeouts
        — groups the barrier FAILED because a follower quorum never
          acked in time; the group's bytes are truncated off the
          leader's WAL and the stream epoch bumps (no divergence)
    storage.repl.streams / storage.repl.bytes_shipped
        — follower tail streams the leader served, and the framed WAL
          bytes shipped down them
    storage.repl.ship_errors
        — ship/ack paths broken by a dead socket or the ``repl.ship``/
          ``repl.ack`` fault points (the follower reconnects/re-acks)
    storage.repl.applied_groups / storage.repl.applied_records
        — groups (and the mutations inside) a follower applied through
          the real recovery path; byte-order == rv-order by invariant
    storage.repl.resyncs
        — followers that re-based on the leader after local state went
          suspect or obsolete (leader epoch moved, offset
          discontinuity, digest mismatch, checkpoint generation moved);
          each resolves as a ckpt_seed or a full_retail
    storage.repl.ckpt_seeds / storage.repl.full_retails
        — how each resync re-based: seeded from a shipped checkpoint
          generation (O(state) bootstrap, DESIGN.md §28) vs wiped and
          re-tailed the leader's FULL WAL from offset 0 (only legal
          against a leader that has never compacted)
    storage.repl.ckpt_published
        — checkpoint generations a LEADING store published at
          compaction (hub rebased: epoch bump, byte space restarted)
    storage.repl.ckpt_ships / storage.repl.ckpt_bytes
        — checkpoint generations served over GET /repl/checkpoint, and
          their body bytes (the bootstrap traffic that replaces
          unbounded history re-tails)
    storage.repl.stale_acks
        — follower acks dropped because they were tagged with a
          RETIRED stream epoch (pre-rebase/pre-retract byte offsets
          must never satisfy a quorum in the restarted space)
    storage.repl.digest_mismatch
        — cross-replica scrub gossip convicted a byte range whose
          CRC32C diverged from the leader's digest ring (bit rot or a
          forked history; the follower resyncs rather than serve it)
    storage.repl.fenced_writes
        — mutations a demoted ex-leader refused with typed NotLeader
          (the fence that makes split-brain writes impossible)
    storage.repl.not_leader_errors
        — remote-client requests answered 503 not-leader (re-discover
          the leader; never blind-retried)
    storage.repl.promotions
        — follower→leader promotions won via arbiter-majority lease CAS
    storage.repl.compact_deferred
        — retired (always 0 since checkpoint shipping landed): WAL
          compactions a leading replica used to skip while a hub was
          attached; kept registered so old dashboards read zero
          instead of breaking
    storage.repl.apply_lag_rv  (gauge)
        — how many rv this follower's applied state trails the leader's
          advertised rv (refreshed on every applied group and on each
          epoch sync; 0 = caught up).  The freshness number behind the
          ``applied_rv`` field /repl/status reports and the bound
          NotYetObserved answers are judged against (DESIGN.md §29)

The follower-serving read plane (ISSUE 17, DESIGN.md §29: rv-bounded
reads off any replica, watch fanout on followers, the endpoint-aware
client) records under ``wire.read.`` / ``remote.`` — the chaos-read
soak's and the readscale bench's evidence:

    wire.read.bounded_requests
        — GET/LIST requests that carried a ``min_rv`` freshness bound
          (REST query param or gRPC List field); every read answer also
          stamps its ``X-Minisched-RV`` watermark, bounded or not
    wire.read.not_yet_observed
        — bounded reads and watch resumes this replica REFUSED typed
          (HTTP 504 / gRPC UNAVAILABLE, ``not yet observed``) because
          its applied rv still trailed the bound: the retryable lag
          signal, never a silently stale 200 — distinct from
          HistoryCompacted's 410, which means relist
    remote.read_failover
        — endpoint-aware reads rotated off a dead, fenced, or lagging
          replica onto the next endpoint (the read cursor moved; the
          request itself is then retried on the new façade)
    remote.not_yet_observed
        — 504 lag answers the endpoint-aware client absorbed (each
          rotates the read cursor in multi-endpoint mode and consumes
          one backoff slot; single-endpoint stores raise typed)
    remote.watch_failover
        — watch streams re-opened on a rotated replica after the
          serving endpoint died or lagged the resume cursor; combined
          with the server's exact rv>resume replay this is the
          exactly-once failover the chaos-read soak audits
    remote.leader_discoveries
        — leader lookups resolved by probing ``/repl/status`` across
          the endpoint list (writes route to the discovered leader;
          invalidated on NotLeader/transport failure and re-discovered)
    informer.resume_not_yet_observed
        — informer watch re-opens answered "not yet observed" by a
          lagging replica: the informer KEEPS its resume cursor and
          backs off (the cache is intact — waiting out lag is cheaper
          than a relist), unlike the 410 path which must relist

The network-fault layer (faults/net.py — the partition nemesis) records
under ``net.partition.``, the chaos-partition soak's injection evidence:

    net.partition.dropped / net.partition.blackholed /
    net.partition.delayed
        — outbound replication-plane calls the layer enforced against:
          refused immediately (drop / scheduled net.drop), hung for the
          caller's timeout then refused (blackhole), or delayed then
          allowed through (one-way latency)
    net.partition.cuts / net.partition.heals
        — link rules imposed and removed (cut()/heal(), including over
          the POST /net/partition control surface)
    net.partition.links  (gauge)
        — imposed link rules currently in force in this process

The gRPC facade's memoized LIST encode (grpcserver._SnapListCache)
mirrors the REST relist cache:

    grpc.list_cache.hits / grpc.list_cache.encodes
        — List RPCs served from the snapshot-keyed memo vs. fresh
          encodes (one per COW snapshot flip per kind; hits/encodes is
          the relist-storm sharing ratio)

The gRPC Watch facade (grpcserver._WatchHub — the REST selector
stream-loop handoff ported to the unary-stream rpc) records under
``grpc.watch.``:

    grpc.watch.streams
        — watch streams adopted by the hub after handshake + sync-line
          (one drain thread serves ALL of them; thread count must not
          scale with stream count)
    grpc.watch.events
        — store events the hub drained and fanned out to its streams
    grpc.watch.encoded / grpc.watch.shared
        — first encode of an event's framed wire bytes (memoized on the
          shared WatchEvent) vs. reuses by every other stream: the
          encode-once claim, same shape as watch.fanout.encoded/shared
    grpc.watch.evicted
        — streams evicted because their bounded buffer overflowed
          (DEFAULT_WATCH_STREAM_EVENTS): the laggard is aborted
          OUT_OF_RANGE — its history is gone from the buffer just as
          surely as from a compacted ring — and recovers via
          relist + resume, never by blocking the hub

The sharded write plane (ISSUE 18, DESIGN.md §30: controlplane/shards —
namespace-partitioned leader groups behind one logical surface) records
the router side under ``shard.`` and the façade/store side under
``storage.shard.``; surfaced in the chaos-shard audits and the bench
``shard`` role's record:

    shard.topology_refreshes
        — router re-fetches of /shards/status after a WrongShard/typed
          refusal or an explicit probe; each adopts the highest epoch
          seen across endpoints
    shard.wrong_shard_chased
        — writes/reads the router re-dispatched after a 421 WrongShard
          refusal + topology refresh (the stale-router chase; bounded
          attempts, then the typed error surfaces)
    shard.cross_bind_batches / shard.cross_bind_entries
        — bind batches that SPANNED >1 leader group (the two-shard
          commit path: one logical batch id, per-group ack ordinals,
          registry replay on retry) and the bindings inside them
    shard.watch_reopen
        — per-group component streams of a merged vector watch reopened
          at that shard's cursor component after a drop/failover (the
          other groups' streams keep flowing meanwhile)
    shard.events_suppressed
        — merged-watch events dropped because the emitting group no
          longer owns the object's namespace under the current topology
          (post-split echoes; the vector cursor still advances)
    shard.splits
        — namespace reassignments completed via the freeze → handoff →
          seed → topology-bump → unfreeze → purge protocol
    storage.shard.wrong_shard_refused / storage.shard.frozen_refused
        — façade-side typed refusals: a write for a namespace this
          group does not own under its topology epoch (421) / for a
          namespace mid-handoff write-freeze (503, retryable — the
          freeze is bounded by the split protocol)
    storage.shard.topology_updates / storage.shard.freezes
        — topology epochs adopted over POST /shards/control, and
          namespace write-freezes imposed there
    storage.shard.handoff_ships / storage.shard.handoff_objects
        — namespace handoff snapshots served over GET /shards/handoff
          (the checkpoint-seed unit of a split) and the objects inside
    storage.shard.seed_objects / storage.shard.purged_objects
        — objects applied from a handoff seed on the receiving group /
          deleted from the source group after ownership flipped
    remote.shard_frozen_retry
        — remote-client requests that absorbed a 503 "shard frozen"
          answer and retried with backoff (rides the split's bounded
          write-freeze instead of failing the caller)

The self-defending shard plane (ISSUE 20, DESIGN.md §31: freeze
leases, the cross-shard capacity mirror, autosplit) adds:

    remote.shard_frozen_timeout
        — frozen-shard waits that exhausted their OWN deadline
          (``RemoteStore(frozen_deadline_s=)``) and surfaced the typed
          ShardFrozenTimeout instead of hammering on: the freeze
          outlived every healthy split's window plus the lease TTL
    storage.shard.freeze_expired
        — freeze leases a replica auto-thawed at TTL expiry (the
          coordinator died or stalled mid-split; the namespace
          un-strands itself with no operator in the loop)
    storage.shard.purge_skipped
        — source-side objects a keyed post-split purge left in place
          because they were NOT in the handoff manifest: writes
          admitted after a lease-expiry thaw — deleting them would be
          acked-write loss
    shard.endpoint_discoveries
        — follower data urls the router's per-group endpoint discovery
          learned from /repl/status beyond the topology document (the
          §29 multi-endpoint read client folded into the shard router)
    shard.budget.mirror_syncs / shard.budget.reports
        — budget-doc refreshes a non-home group's mirror adopted
          (rv-monotonic; stale fetches dropped) / per-group usage
          reports the home group's board folded in (rv-monotonic per
          reporting group)
    shard.budget.mirror_checks / shard.budget.unknown_node /
    shard.budget.refused
        — bind budget lookups answered from the cross-shard mirror
          (Node absent from the local store), lookups the mirror could
          not answer (Node unknown — no check, matching the
          reference's unvalidated bind), and binds REFUSED on the
          mirror's verdict (the OutOfCapacity carries its
          ``budget-mirror rv=`` watermark)
    sched.bind_mirror_refusals
        — engine bind failures whose OutOfCapacity carried the
          budget-mirror watermark: cross-shard capacity said no —
          sync-lag signal, counted apart from local capacity races
    shard.autosplit.samples / shard.autosplit.hot
        — load-watcher ticks, and ticks whose windowed
          storage.group_wait_s p99 or live group-commit stage depth
          crossed the hot thresholds (hysteresis: ``hot_samples``
          consecutive hot ticks arm a split)
    shard.autosplit.triggered / shard.autosplit.skipped /
    shard.autosplit.errors
        — autosplits fired (hottest owned namespace to the rendezvous
          pick among the other groups), armed triggers skipped
          (cooldown window, fenced store, or no eligible namespace),
          and split attempts that raised (next tick retries)
"""

from __future__ import annotations

import threading
from typing import Dict, Set


#: what the device engine's lanes count (see the module doc), registered
#: at 0 when a device engine is constructed: a reader of /metrics tells a
#: lane that did not run from a program that has no such counter
LANE_COUNTERS = (
    "scan.rows_live", "scan.rows_total", "scan.rows_narrow",
    "scan.calls_wide", "scan.calls_narrow",
    "scan.combos_live", "scan.combos_total",
    "sched.lane_pods.wave", "sched.lane_pods.wide",
    "sched.lane_pods.narrow", "sched.lane_pods.exact",
    "scan.excl_terms", "scan.excl_nodes", "scan.excl_capacity",
    "sched.evaluated_pods", "sched.unschedulable_pods",
    "constraint_index.pods_removed",
)


class Counters:
    def __init__(self) -> None:
        self._mu = threading.Lock()
        self._counts: Dict[str, int] = {}
        self._gauge_names: Set[str] = set()

    def inc(self, name: str, n: int = 1) -> None:
        with self._mu:
            self._counts[name] = self._counts.get(name, 0) + n

    def set_gauge(self, name: str, n: int) -> None:
        """Last-write-wins value for state-shaped entries (a mesh
        factoring, a shard count) — engine restarts and multi-engine
        processes must not sum them into nonsense.  The name is
        remembered as gauge-typed so the Prometheus exposition
        (observability/hist.render_prometheus) emits the right # TYPE."""
        with self._mu:
            self._counts[name] = n
            self._gauge_names.add(name)

    def gauge_names(self) -> Set[str]:
        with self._mu:
            return set(self._gauge_names)

    def get(self, name: str) -> int:
        with self._mu:
            return self._counts.get(name, 0)

    def snapshot(self) -> Dict[str, int]:
        with self._mu:
            return dict(self._counts)

    def reset(self) -> None:
        with self._mu:
            self._counts.clear()
            self._gauge_names.clear()


GLOBAL = Counters()


def inc(name: str, n: int = 1) -> None:
    GLOBAL.inc(name, n)


def set_gauge(name: str, n: int) -> None:
    GLOBAL.set_gauge(name, n)


def get(name: str) -> int:
    return GLOBAL.get(name)


def snapshot() -> Dict[str, int]:
    return GLOBAL.snapshot()


def reset() -> None:
    GLOBAL.reset()
