"""Global flag system — the gflags-compatible env bridge.

Reference: ~45 DEFINE_* gflags in paddle/fluid/platform/flags.cc, plus the
env whitelist that Python forwards at import
(python/paddle/fluid/__init__.py:162-210 read_env_flags ->
core.init_gflags).

TPU-native mapping: flags that configured CUDA memory/streams are accepted
and recorded (scripts that set them keep working); flags with live TPU
equivalents are wired up:

- FLAGS_check_nan_inf      -> per-op NaN/Inf checking in the executor
                              (reference operator.cc:945) + jax debug_nans
- FLAGS_cudnn_deterministic / FLAGS_cpu_deterministic -> recorded; XLA
                              compilation is deterministic by construction
- FLAGS_fraction_of_gpu_memory_to_use -> XLA_PYTHON_CLIENT_MEM_FRACTION
- communicator_* flags     -> defaults for fluid.communicator.Communicator
- rpc_deadline             -> RPC client/server timeouts (distributed_ops)
"""

from __future__ import annotations

import os

# name -> default. The union of the reference's env-settable whitelist and
# the flags its Python layer reads back.
_DEFAULTS = {
    # numerics / debugging
    "check_nan_inf": False,
    # int8-wire gradient allreduce (EQuARX-style,
    # parallel/quantized_allreduce.py): c_allreduce_sum on the data axis
    # quantizes its payload when enabled
    "quantized_allreduce": False,
    "fast_check_nan_inf": False,
    "benchmark": False,
    "cpu_deterministic": False,
    "cudnn_deterministic": False,
    # memory (recorded; XLA owns memory)
    "eager_delete_scope": True,
    "initial_cpu_memory_in_mb": 500,
    "init_allocated_mem": False,
    "eager_delete_tensor_gb": 0.0,
    "fast_eager_deletion_mode": True,
    "memory_fraction_of_eager_deletion": 1.0,
    "allocator_strategy": "naive_best_fit",
    "fraction_of_gpu_memory_to_use": 0.92,
    "use_pinned_memory": True,
    # threading
    "paddle_num_threads": 1,
    "dist_threadpool_size": 0,
    "inner_op_parallelism": 0,
    # reader
    "reader_queue_speed_test_mode": False,
    # double-buffered device feed: how many decoded+device_put batches the
    # background producer may run ahead of the consuming step (reference:
    # buffered_reader.cc kDoubleBufferSize; 2 = classic double buffering —
    # deeper queues pin more HBM for no extra overlap)
    "reader_buffer_size": 2,
    # serving runtime (paddle_tpu/serving): micro-batch coalescer policy.
    # max_batch_size caps how many request rows one device batch carries
    # (also the top of the default padding-bucket ladder); batch_timeout_ms
    # bounds how long the coalescer holds the first request of a batch
    # waiting for more; queue_depth bounds admission (beyond it requests
    # are SHED with retry-after instead of queuing unboundedly); workers
    # sizes the predictor pool / dispatch threads.
    "serving_max_batch_size": 8,
    "serving_batch_timeout_ms": 5.0,
    "serving_queue_depth": 64,
    "serving_workers": 2,
    # default per-request deadline; 0 = no deadline. Requests whose
    # deadline passes while queued are shed at dispatch time.
    "serving_default_deadline_ms": 0.0,
    # autoregressive decode runtime (paddle_tpu/serving/decode.py): the
    # KV-cache slot pool + continuous batching engine. decode_slots sizes
    # the cache pool (= max concurrent streams per engine);
    # decode_max_len caps the per-slot cache length (0 = the model's
    # max_position_embeddings); decode_prefill_buckets overrides the
    # powers-of-two prompt-length ladder with an explicit CSV ("16,64");
    # decode_queue_depth bounds admission (beyond it submissions shed
    # with retry-after, like the micro-batcher).
    "decode_slots": 8,
    "decode_max_len": 0,
    "decode_prefill_buckets": "",
    "decode_queue_depth": 64,
    # the KV cache is ONE shared pool of decode_block_size-token blocks
    # addressed through per-slot block tables: a slot's footprint is
    # ceil(len/block) blocks, and the block is also the prefix reuse
    # granularity (must be >= 1; 16 is what the gpt2s-serve-chat cell and
    # chip_smoke.py run). decode_prefix_cache_mb bounds how many pool
    # blocks the zero-copy prefix index may pin (0 = prefix caching off;
    # a prompt reuses its longest cached whole-block prefix, hash-chain
    # keyed and token-verified, by a table edit); decode_prefill_chunk
    # caps how many prompt tokens one engine tick may prefill (0 = one
    # window at admission) so a long prompt admits as bucket-shaped
    # windows interleaved with the fused decode steps instead of
    # stalling live streams.
    "decode_block_size": 16,
    "decode_prefix_cache_mb": 0.0,
    "decode_prefill_chunk": 0,
    # decode_spec_tokens = k > 1 arms speculative decoding: a k-1-token
    # draft per slot per tick, ONE batched verify program scoring all k
    # positions, and host-side longest-matching-prefix acceptance that
    # stays token-exact with sequential decoding (greedy and
    # seeded-sampled). decode_spec_draft picks the drafter: "ngram"
    # (self-draft from the stream's own history) or "repeat" (last-token
    # run-length); a small-model drafter plugs in via
    # DecodeEngine(drafter=...).
    "decode_spec_tokens": 0,
    "decode_spec_draft": "ngram",
    # SPMD mesh (paddle_tpu/parallel/spmd.py): spmd_decode_tp > 1 serves
    # DecodeSession/DecodeEngine tensor-parallel over a {"model": tp}
    # mesh (weights Megatron column/row-sharded, KV pools
    # heads-partitioned, block tables replicated) via the GSPMD path;
    # mesh_force_host_devices arms
    # XLA_FLAGS=--xla_force_host_platform_device_count=N through
    # spmd.ensure_virtual_devices() so a CPU-only box exposes N virtual
    # devices for single-process multi-device SPMD (0 = leave the
    # environment alone; only effective before jax initializes).
    "spmd_decode_tp": 1,
    "mesh_force_host_devices": 0,
    # fleet KV tier (paddle_tpu/serving/kv_tier.py): tiered prefix-block
    # cache over the paged pool. kv_tier_host_mb sizes the host-spill
    # store (LRU-evicted device blocks spill D2H and re-admit H2D on a
    # later chain hit; 0 = off, blocks vanish on eviction as before).
    # kv_tier_advert_k bounds the hot chain-head keys each replica
    # advertises via /readyz for the router's cache-affinity scoring;
    # kv_tier_advert_ttl_s is the router-side staleness bound past which
    # an advertisement is ignored (a dead replica's heads can't
    # black-hole traffic). The role-split pull path: the controller
    # writes prefill-replica endpoints to kv_tier_peers_file; a
    # decode-role replica whose admission would cache fewer than
    # kv_tier_pull_min_tokens prompt tokens locally pulls published
    # blocks from a peer first (per-request budget
    # kv_tier_pull_timeout_s; any failure degrades to local prefill).
    "kv_tier_host_mb": 0.0,
    "kv_tier_advert_k": 8,
    "kv_tier_advert_ttl_s": 5.0,
    "kv_tier_peers_file": "",
    "kv_tier_pull_min_tokens": 0,
    "kv_tier_pull_timeout_s": 2.0,
    # HTTP serving gateway (paddle_tpu/serving/gateway.py): the network
    # front door over InferenceServer (+ attached DecodeEngine).
    # gateway_port binds the listener (0 = ephemeral — tests/probes read
    # the bound port back); admission control in FRONT of the engine:
    # gateway_rate_limit_rps is a PER-TENANT token-bucket refill rate
    # (0 = unlimited) with gateway_rate_burst capacity,
    # gateway_tenant_max_inflight caps one tenant's concurrently served
    # requests (0 = unlimited; the isolation knob — a flooding tenant
    # 429s at its own quota instead of starving the others),
    # gateway_max_inflight caps the whole gateway (beyond it requests
    # WAIT in priority order — interactive before batch — up to
    # gateway_admit_timeout_ms, then shed 429). gateway_drain_timeout_s
    # bounds the graceful drain (SIGTERM/stop waits for in-flight
    # streams before closing the listener); gateway_access_log appends
    # one JSONL line per request to the given path ("" = off), rotated
    # (keep-1 rollover to <path>.1) the moment it passes
    # gateway_access_log_max_mb (0 = unbounded).
    "gateway_port": 0,
    "gateway_rate_limit_rps": 0.0,
    "gateway_rate_burst": 20,
    "gateway_tenant_max_inflight": 0,
    "gateway_max_inflight": 64,
    "gateway_admit_timeout_ms": 100.0,
    "gateway_drain_timeout_s": 30.0,
    "gateway_access_log": "",
    "gateway_access_log_max_mb": 0.0,
    # serving fleet control plane (paddle_tpu/serving/fleet.py): a
    # FleetController supervises N replica processes (each an
    # InferenceServer+Gateway) behind one Router. The load-driven
    # autoscaler scrapes each replica's /metrics every
    # fleet_scale_interval_s and scales the pool between
    # fleet_min_replicas and fleet_max_replicas: mean queue depth >=
    # fleet_queue_high (or any admission shed, or — when
    # fleet_latency_high_ms > 0 — p95 latency over it) sustained for
    # fleet_scale_up_ticks consecutive scrapes adds a replica; queue
    # depth <= fleet_queue_low for fleet_scale_down_ticks scrapes
    # (hysteresis, so the pool doesn't flap) drains one. A replica must
    # turn ready within fleet_replica_ready_timeout_s of spawn; crashed
    # replicas are replaced with fleet_restart_backoff_s exponential
    # backoff under a fleet_max_replica_restarts budget; scale-down and
    # rollout drains SIGTERM the replica (gateway graceful drain) and
    # SIGKILL only after fleet_drain_grace_s.
    "fleet_min_replicas": 1,
    "fleet_max_replicas": 4,
    "fleet_scale_interval_s": 2.0,
    "fleet_queue_high": 8.0,
    "fleet_queue_low": 1.0,
    "fleet_latency_high_ms": 0.0,
    "fleet_scale_up_ticks": 2,
    "fleet_scale_down_ticks": 5,
    "fleet_replica_ready_timeout_s": 180.0,
    "fleet_restart_backoff_s": 0.5,
    "fleet_max_replica_restarts": 10,
    "fleet_drain_grace_s": 15.0,
    # autoscaler policy selection: fleet_policy picks the controller's
    # scaling brain — "streak" is the load-driven AutoscalerPolicy above;
    # "slo" is SLOPolicy, which scales on scraped per-replica p95 TTFT
    # (fleet_slo_ttft_ms) / p95 inter-token latency
    # (fleet_slo_intertoken_ms) budgets instead of raw queue depth (0
    # disarms a budget; sheds always count as breach). Scale-down needs
    # every armed p95 under fleet_slo_headroom * budget (plus zero
    # sheds) sustained for the same fleet_scale_down_ticks hysteresis.
    "fleet_policy": "streak",
    "fleet_slo_ttft_ms": 2000.0,
    "fleet_slo_intertoken_ms": 0.0,
    "fleet_slo_headroom": 0.6,
    # control-plane durability (crash-safe controller): every replica
    # refreshes a lease stamp in its endpoint file every
    # fleet_lease_interval_s; a lease older than fleet_lease_ttl_s
    # means the replica is dead or wedged (a restarted controller will
    # not adopt it, a running one kills it). The controller journals
    # its own lease into workdir/fleet_state.json — a second
    # controller starting on the same workdir refuses to double-
    # supervise while that lease is younger than
    # fleet_state_lease_ttl_s AND the journaled pid is alive
    # (split-brain guard); a stale lease or a dead pid means the
    # previous controller crashed, and the newcomer adopts the
    # surviving replica pool instead of respawning it.
    "fleet_lease_interval_s": 1.0,
    "fleet_lease_ttl_s": 5.0,
    "fleet_state_lease_ttl_s": 10.0,
    # decode-slot scheduler (paddle_tpu/serving/decode.py): pending
    # admissions dequeue weighted-fair across tenants (stride scheduling;
    # sched_tenant_weights is "tenantA:4,tenantB:1" — unlisted tenants
    # weigh 1.0) with interactive class strictly ahead of batch. When
    # sched_preempt is on and an interactive request is waiting with no
    # free slot, the engine evicts a batch generation mid-stream (its
    # prompt + emitted tokens re-prefill on re-admission, so the resumed
    # stream is token-exact) instead of making interactive queue behind
    # it.
    "sched_preempt": True,
    "sched_tenant_weights": "",
    # fleet simulator (paddle_tpu/serving/sim): virtual-clock replay of
    # recorded/synthetic workloads through the real policy + admission +
    # router classes. sim_replica_ready_s models the spawn-to-ready lag
    # of a scaled-up replica inside the simulation.
    "sim_replica_ready_s": 5.0,
    # replica router (paddle_tpu/serving/router.py): the fleet's single
    # front door. router_port binds the listener (0 = ephemeral); a
    # health thread polls every backend's /readyz each
    # router_health_interval_s; idempotent /v1/infer requests that hit a
    # dead/draining replica are retried on another backend up to
    # router_retries times; router_backend_timeout_s bounds each proxied
    # backend connect/read.
    "router_port": 0,
    "router_health_interval_s": 0.5,
    "router_retries": 2,
    "router_backend_timeout_s": 60.0,
    # durable streaming generations: a pinned /v1/generate stream whose
    # replica dies (or times out) mid-stream is re-admitted on a healthy
    # replica with the already-emitted token suffix (token-exact resume)
    # up to router_generate_retries times, within the request deadline.
    # 0 disables failover (mid-stream death degrades to the in-band
    # error event).
    "router_generate_retries": 2,
    # per-backend circuit breaker: router_breaker_failures consecutive
    # request-path failures open the breaker (the backend is excluded
    # from routing even while /readyz answers 200 — a flapping replica
    # can't eat one retry from every in-flight request); after
    # router_breaker_cooldown_s the breaker goes half-open and admits a
    # single probe request, which closes it on success or re-opens it
    # on failure. 0 failures disables the breaker.
    "router_breaker_failures": 3,
    "router_breaker_cooldown_s": 2.0,
    # the router's own JSONL access log (the fleet's PUBLIC front door:
    # one line per request with trace_id, backend chosen, retries,
    # failover count; "" = off), same writer + size rotation as the
    # gateway's (router_access_log_max_mb, 0 = unbounded).
    "router_access_log": "",
    "router_access_log_max_mb": 0.0,
    # distributed tracing (observability/trace.py + fleet_trace.py):
    # trace_flight_records bounds the per-process flight-recorder ring
    # (one journey record per request, dumped to FLAGS_obs_dir on
    # drain/error/snapshot); trace_dump_spans bounds the black-box span
    # dump (trace_rank_<r>.json) written beside it, the newest-N spans
    # a dead process leaves for the fleet merge.
    "trace_flight_records": 256,
    "trace_dump_spans": 4096,
    # checkpoint manager (paddle_tpu/checkpoint): trainer-integrated save
    # cadence (0 = off), retention (newest keep_max steps survive GC,
    # every keep_every_n_steps-th step is pinned forever), writer-queue
    # depth (snapshots in flight before save() back-pressures), and how
    # long rank 0 waits for peer shard manifests before failing a
    # sharded commit.
    "ckpt_save_interval_steps": 0,
    "ckpt_keep_max": 5,
    "ckpt_keep_every_n_steps": 0,
    "ckpt_async_depth": 2,
    "ckpt_commit_timeout_s": 120.0,
    # resume resilience: when the newest committed checkpoint fails its
    # crc32 manifest check, restore_or_initialize logs the ChecksumError
    # and falls back to the next-newest valid step instead of hard-failing
    "ckpt_restore_fallback": True,
    # background checkpoint scrubbing: after each commit the writer
    # thread re-verifies committed steps' checksums off the critical
    # path (ckpt_scrub_ok/_corrupt counters), so the guardian's rollback
    # target is always a known-good step, not merely the newest one
    "ckpt_scrub": False,
    # training guardian (paddle_tpu/distributed/guardian.py): data-plane
    # anomaly defense wired through fluid/trainer.py. guardian_enable
    # arms the in-graph health fetch (global grad-norm + isfinite folded
    # into the step program) and the host-side anomaly policy: NaN/Inf
    # is immediate; loss spikes / grad-norm explosions are judged by a
    # robust rolling window (EWMA center, MAD scale) at
    # guardian_spike_sigma z-score over guardian_spike_window samples
    # after guardian_warmup_steps. The graduated response ladder:
    # skip-step (discard the update, advance the stream) up to
    # guardian_max_skips times, then rollback to the newest VERIFIED
    # checkpoint up to guardian_max_rollbacks times (dropping the
    # poisoned batch window on replay), then structured giveup.
    # guardian_marker_dir persists poisoned-step markers across process
    # restarts (chaos-style one-shot: a deterministic bad batch can
    # never rollback-loop); guardian_digest_interval > 0 publishes a
    # cross-replica state digest through the heartbeat file every N
    # steps for the supervisor's SDC majority vote (0 = off).
    "guardian_enable": False,
    "guardian_spike_sigma": 6.0,
    "guardian_spike_window": 64,
    "guardian_warmup_steps": 8,
    "guardian_max_skips": 2,
    "guardian_max_rollbacks": 1,
    "guardian_digest_interval": 0,
    "guardian_marker_dir": "",
    # elastic supervisor (paddle_tpu/distributed/supervisor.py): hang
    # watchdog threshold over worker heartbeat files, worker-side beat
    # write throttle, and the restart backoff (base doubles per restart,
    # capped, with decorrelating jitter)
    "dist_heartbeat_timeout_s": 60.0,
    "dist_heartbeat_interval_s": 0.5,
    # staleness bound for an INSTRUMENTED worker still pre-first-step
    # (status "start": restore + first XLA compile) — generous but
    # finite so a post-restart deadlock cannot stall the gang forever
    "dist_startup_grace_s": 600.0,
    "dist_restart_backoff_s": 1.0,
    "dist_restart_backoff_max_s": 30.0,
    # separate restart budget for PREEMPTED workers (exit 143 / SIGTERM
    # death / unspawnable slot): on a preemptible pool preemptions are
    # the normal lifecycle, so the default is generous — a crash-looping
    # worker still burns --max_restarts
    "dist_max_preempt_restarts": 100,
    # elastic resize (distributed/elastic.py + supervisor): a restart
    # may shrink the gang to the launchable survivors down to this
    # floor, remapping rank ids contiguously and growing back when
    # downed slots return; 0 = fixed-size restarts only.
    "elastic_min_world_size": 0,
    # opt-in linear LR rescaling for degraded attempts: per-rank batch
    # stays fixed, so the global batch shrinks by world/base — scale the
    # program's global learning-rate var(s) by the same factor (applied
    # relative to the world size the checkpoint was saved at, so resumes
    # never compound it). Off by default: identical-replica workloads
    # must NOT rescale.
    "elastic_lr_rescale": False,
    # deterministic fault injection (paddle_tpu/testing/chaos.py):
    # -1/0/"" = disarmed; target_rank scopes step faults to one gang
    # member; marker_dir makes each fault one-shot across gang restarts
    "chaos_crash_at_step": -1,
    "chaos_hang_at_step": -1,
    # slice-preemption fault: the worker occupying gang slot
    # chaos_lose_rank writes its down marker (PADDLE_TPU_DOWN_FILE) at
    # step chaos_lose_rank_at_step and exits 143; the slot stays
    # unlaunchable for chaos_lose_rank_for supervisor planning rounds
    # (-1 = until the marker is deleted), making shrink->regrow
    # deterministically reproducible
    "chaos_lose_rank": -1,
    "chaos_lose_rank_at_step": -1,
    "chaos_lose_rank_for": -1,
    # data-plane faults for the training guardian's closed loop:
    # chaos_nan_grad_at_step poisons the armed step's feed batch with a
    # NaN (loss and every grad go non-finite — detection must be
    # within one step); chaos_loss_spike_at_step scales the batch so
    # the loss spikes while staying finite (the robust-window path);
    # chaos_bitflip_grad_at_step flips the sign bit of one parameter
    # element AFTER the armed step's update on the chaos_target_rank
    # worker — silent data corruption only the cross-replica digest
    # vote can see
    "chaos_nan_grad_at_step": -1,
    "chaos_loss_spike_at_step": -1,
    "chaos_bitflip_grad_at_step": -1,
    "chaos_corrupt_ckpt": False,
    "chaos_slow_feed_ms": 0.0,
    "chaos_rpc_fail_n": 0,
    "chaos_target_rank": -1,
    "chaos_marker_dir": "",
    # mid-stream serving fault: the replica process SIGKILLs itself
    # after writing exactly chaos_die_after_tokens SSE stream tokens
    # (process-wide count), scoped to the replica whose
    # PADDLE_TPU_REPLICA_ID matches chaos_die_replica (-1 = any) — the
    # deterministic rig behind the router failover trials
    "chaos_die_after_tokens": -1,
    "chaos_die_replica": -1,
    # control-plane fault: the FLEET CONTROLLER process SIGKILLs itself
    # chaos_kill_controller_after_s seconds after its control loop
    # starts (its replicas keep serving headless) — the deterministic
    # rig behind the controller-crash / replica-adoption probe trial.
    # One-shot under chaos_marker_dir like every chaos fault, so the
    # RESTARTED controller in the same trial does not re-fire it.
    "chaos_kill_controller_after_s": -1.0,
    # observability (paddle_tpu/observability): one telemetry spine over
    # tracing + metrics. obs_trace gates the span tracer (on by default —
    # bounded ring buffer, ~µs per span, measured <2% of the step path by
    # tools/obs_probe.py); obs_trace_buffer bounds retained spans.
    # obs_http_port exposes /metrics /healthz /trace over stdlib HTTP:
    # -1 disabled, 0 ephemeral, >0 binds that port or walks up to
    # obs_http_port_retries successors when taken. obs_dir turns on
    # per-rank JSONL metric snapshots (the gang supervisor injects it so
    # it can merge a cross-rank report); obs_snapshot_interval_s paces
    # periodic snapshots (0 = one final snapshot only).
    "obs_trace": True,
    "obs_trace_buffer": 65536,
    "obs_http_port": -1,
    "obs_http_port_retries": 8,
    "obs_dir": "",
    "obs_snapshot_interval_s": 0.0,
    # device-plane telemetry (observability/xla_stats.py): compile
    # records + recompile sentinel ride the executor's AOT
    # lower-and-compile path. obs_compile_census runs XLA cost analysis
    # + the optimized-HLO op census on every freshly compiled executable
    # (compile time only — the executable is already in hand, no second
    # compile) and publishes per-program-key flops/bytes gauges;
    # obs_compile_records bounds the retained record ring.
    "obs_compile_census": True,
    "obs_compile_records": 1024,
    # strict serving gate: once InferenceServer warmup completes, any
    # steady-state XLA compile raises SteadyStateRecompileError with the
    # sentinel's attribution (instead of only bumping
    # serving_steady_recompiles) — the "0 recompiles after warmup"
    # serving claim as an enforced invariant
    "serving_strict_compiles": False,
    # profiling / graphs
    "print_sub_graph_dir": "",
    "pe_profile_fname": "",
    "tracer_profile_fname": "",
    "dygraph_debug": False,
    "enable_parallel_graph": False,
    "multiple_of_cupti_buffer_size": 1,
    # fusion knobs (XLA fuses; recorded)
    "fuse_parameter_groups_size": 3,
    "fuse_parameter_memory_size": -1,
    # distributed / rpc
    "rpc_deadline": 180000,
    "rpc_retry_times": 3,
    "rpc_server_profile_path": "./profile_ps",
    "enable_rpc_profiler": False,
    "rpc_send_thread_num": 12,
    "rpc_get_thread_num": 12,
    "rpc_prefetch_thread_num": 12,
    "rpc_disable_reuse_port": False,
    "rpc_retry_bind_port": 3,
    "worker_update_interval_secs": 900,
    # pserver liveness + serve-loop bound (HeartBeatMonitor,
    # heart_beat_monitor.h:54; stale threshold is 2 min in the reference)
    "pserver_heartbeat_timeout_s": 120.0,
    "pserver_heartbeat_interval_s": 10.0,
    "pserver_timeout_ms": 600000,
    # trainer-side RPC resilience: transient connection errors during a
    # pserver (re)start retry with capped exponential backoff + jitter up
    # to this many times (overall time still bounded by the
    # FLAGS_rpc_deadline budget)
    "pserver_rpc_retries": 5,
    # communicator
    "communicator_independent_recv_thread": True,
    "communicator_send_queue_size": 20,
    "communicator_min_send_grad_num_before_recv": 20,
    "communicator_thread_pool_size": 5,
    "communicator_max_merge_var_num": 20,
    "communicator_merge_sparse_bucket": 2000,
    "communicator_fake_rpc": False,
    "communicator_send_wait_times": 5,
    "communicator_merge_sparse_grad": True,
    "communicator_is_sgd_optimizer": True,
    # TPU layout: lower conv2d internally as NHWC/HWIO (channels on the
    # lane dimension, the layout the MXU wants) while the API stays NCHW
    "conv_nhwc": True,
    # misc
    "max_body_size": 2147483647,
    "sync_nccl_allreduce": False,
    "use_mkldnn": False,
    "use_ngraph": False,
}

_flags = {}
_explicit = set()  # flags set via env or set_flags (side effects key off it)
_version = 0  # bumped on every mutation; cheap cache-invalidation token


def version():
    """Monotonic counter bumped by set_flags/_read_env — lets hot paths
    cache flag-derived state (e.g. testing.chaos's disarmed fast path)
    and revalidate with one integer compare."""
    return _version


def _coerce(default, text):
    if isinstance(default, bool):
        return text.lower() in ("1", "true", "yes", "on")
    if isinstance(default, int):
        return int(text)
    if isinstance(default, float):
        return float(text)
    return text


def _read_env():
    global _version
    _flags.clear()
    _flags.update(_DEFAULTS)
    _explicit.clear()
    for name, default in _DEFAULTS.items():
        env = os.environ.get("FLAGS_" + name)
        if env is not None:
            try:
                _flags[name] = _coerce(default, env)
                _explicit.add(name)
            except ValueError:
                pass
    # bump AFTER the mutation: a concurrent reader that snapshots the
    # old values under the new version would otherwise cache stale state
    # forever (the bump-after order makes such a race self-healing)
    _version += 1
    _apply_side_effects()


def _apply_side_effects():
    if "check_nan_inf" in _explicit:
        # per-op NaN propagation checks (reference operator.cc:945; jax
        # re-runs the offending primitive un-jitted and points at it).
        # Mirrors the current value, so turning the flag off works too.
        try:
            import jax

            jax.config.update(
                "jax_debug_nans", bool(_flags.get("check_nan_inf"))
            )
        except Exception:
            pass
    if (
        "fraction_of_gpu_memory_to_use" in _explicit
        and "XLA_PYTHON_CLIENT_MEM_FRACTION" not in os.environ
    ):
        os.environ["XLA_PYTHON_CLIENT_MEM_FRACTION"] = str(
            _flags.get("fraction_of_gpu_memory_to_use")
        )


def get_flags(names):
    """paddle-compatible flag read: str or list -> {name: value}."""
    if isinstance(names, str):
        names = [names]
    out = {}
    for n in names:
        key = n[6:] if n.startswith("FLAGS_") else n
        if key not in _flags:
            raise ValueError("flag %r is not registered" % n)
        out[n if n.startswith("FLAGS_") else "FLAGS_" + key] = _flags[key]
    return out


def set_flags(flags):
    """paddle-compatible flag write: {FLAGS_name: value}. Validates (and
    coerces) EVERY key before mutating ANY: a bad key mid-dict must not
    leave earlier keys half-applied with no version bump / side effects
    (version-keyed caches would then serve stale state indefinitely)."""
    global _version
    staged = {}
    for n, v in flags.items():
        key = n[6:] if n.startswith("FLAGS_") else n
        if key not in _DEFAULTS:
            raise ValueError("flag %r is not registered" % n)
        staged[key] = _coerce(_DEFAULTS[key], str(v)) if isinstance(
            v, str
        ) else v
    _flags.update(staged)
    _explicit.update(staged)
    _version += 1  # after the mutation — see _read_env
    _apply_side_effects()


def is_registered(name):
    key = name[6:] if name.startswith("FLAGS_") else name
    return key in _DEFAULTS


def is_explicit(name):
    """True when the flag was set via env or set_flags (vs. sitting at
    its default) — lets risky behaviors distinguish an operator's
    deliberate opt-in from a default."""
    key = name[6:] if name.startswith("FLAGS_") else name
    return key in _explicit


def get_flag(name, default=None):
    key = name[6:] if name.startswith("FLAGS_") else name
    return _flags.get(key, default)


_read_env()
