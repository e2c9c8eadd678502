"""Global flag registry + env bootstrap.

Capability analog of the reference's gflags plumbing
(python/paddle/fluid/__init__.py:92-146 __bootstrap__ reads FLAGS_* from
the environment into core; platform/init.cc consumes them). Flags here
control host-side framework behavior; device behavior belongs to XLA
flags (XLA_FLAGS), which this registry deliberately does not wrap.

Known flags:
  check_nan_inf          per-op NaN/Inf scan in the Executor (debug mode:
                         ops run eagerly, unfused — reference
                         operator.cc:749 semantics)
  benchmark              reserved (reference profiler cadence knob)
  eager_delete_scope     accepted for script compat (scope GC is
                         automatic here)
  fraction_of_gpu_memory_to_use / init_allocated_mem / use_pinned_memory
                         accepted for script compat (PJRT owns memory)
  use_pallas_fused_ops   route eligible op patterns (1x1 conv+BN) through
                         the Pallas fused kernels (paddle_tpu/pallas/)
  use_flash_attention    route eligible attention shapes (T, d lane-
                         aligned) through the Pallas flash kernel
                         (paddle_tpu/pallas/flash_attention.py) — the
                         long-context memory-wall kernel; default ON,
                         falls back to the naive contraction otherwise
  pallas_interpret       run Pallas kernels in interpreter mode off-TPU
                         (numerics tests on CPU)
  fault_plan             deterministic fault injection for the RPC layer
                         (distributed/resilience.py): a JSON FaultPlan,
                         a path to one, or "seed:N" for a generated
                         plan. Per-process via FLAGS_fault_plan env.
  rpc_max_retries / rpc_retry_backoff / rpc_retry_max_backoff /
  rpc_reconnect_secs     shared RetryPolicy for PSClient/MasterClient
                         transparent reconnect (attempts, initial and
                         max backoff seconds, per-attempt reconnect
                         budget)
  rpc_dedup_window       per-trainer replayed-request dedup window on
                         the ParameterService (entries, not seconds)
  trainer_step_retries / trainer_max_rollbacks
                         Trainer.train fault handling: re-run a step
                         this many times on retryable RPC failure, and
                         roll back to the last SUCCESS checkpoint at
                         most this many times on fatal failure
  trainer_incarnation    logical restart counter of this trainer
                         process (elastic recovery): pservers fence
                         messages from lower incarnations and rejoin
                         higher ones; the supervisor bumps it per
                         restart
  ps_state_path          pserver durability: atomic snapshot file for
                         params + round/replay state ('' = off);
                         mutations since the snapshot journal to
                         <path>.journal
  ps_snapshot_every      rounds between pserver snapshots
  ps_average_live        average merged gradients over the LIVE
                         trainer set instead of the original
                         num_trainers (see ParameterService._merge)
  ps_check_grad_finite   pserver-side guard (default on): reject a
                         SEND_VAR with NaN/Inf in its float payload
                         with a retryable error BEFORE journaling or
                         applying it — the client retry resends the
                         value it actually computed
  rpc_read_deadline      socket read deadline (seconds) for PSClient /
                         MasterClient: a peer that accepts but never
                         replies surfaces as RetryableRPCError instead
                         of a silent hang
  rpc_inflight_window    pipelined PSClient: max unacked requests
                         riding one connection (the *_async APIs);
                         1 degrades to stop-and-wait
  rpc_batch_bytes        dense gradients up to this many bytes bound
                         for one endpoint coalesce into a single
                         SEND_VARS frame (0 disables batching)
  rpc_batch_max_bytes /
  rpc_batch_max_vars     flush thresholds for one SEND_VARS frame
                         (total payload bytes / contained vars)
  anomaly_action         Trainer numeric-anomaly guard: 'none' (off,
                         default), 'rollback' (skip the step; after
                         anomaly_skip_steps consecutive anomalies,
                         roll back to the last SUCCESS checkpoint), or
                         'fatal' (raise once the skip budget is spent)
  anomaly_skip_steps     consecutive anomalous steps tolerated (as
                         skipped steps) before the anomaly_action
                         escalation fires
  obs_dir                observability root (paddle_tpu/obs/): when set,
                         the telemetry registry exports metric
                         snapshots and the trace layer drains its span
                         buffer (RecordEvent scopes, RPC spans) and
                         writes fault records as JSONL under this
                         directory ('' = observability off, the
                         default — every instrument is a near-free
                         no-op). The Supervisor gives each role its
                         own subdir; tools/obs_report.py merges them.
  obs_role               label stamped on every JSONL record this
                         process writes (defaults to 'pid<pid>');
                         becomes the timeline lane name
  obs_flush_secs         seconds between periodic metric-snapshot
                         export lines (a final line is flushed at
                         clean exit regardless)
  serving_slots          slot-pool size per PagedDecodePredictor
                         (paddle_tpu/serving/): decode runs one
                         compiled step over this many lanes
  serving_max_queue      ServingEngine admission queue bound — submit()
                         past this raises instead of buffering
                         unboundedly
  serving_idle_wait      seconds an idle serving worker sleeps between
                         queue polls
  serving_page_tokens    paged KV cache: tokens per page (page-pool
                         granularity for alloc/COW/prefix sharing)
  serving_kv_pages       paged KV cache: physical pages in the pool
                         (0 = auto-size to a full window per slot,
                         slots * ceil(max_len/page_tokens) + 1)
  serving_prefill_chunk  chunked prefill: tokens admitted per engine
                         iteration while a prompt prefills, so long
                         prompts never stall live decode lanes
  serving_preempt_policy paged-cache exhaustion response
                         (serving/preempt.py): 'swap' preempts the
                         lowest-tier longest-idle stream and copies its
                         pages to host RAM (falling back to
                         drop-and-re-prefill when the host budget is
                         dry), 'reprefill' always drops pages and
                         re-prefills from the accumulated tokens on
                         resume, 'off' restores the legacy behavior
                         (fail the victim typed; the fleet router
                         retries it as a shed)
  serving_swap_host_mb   host-RAM budget (MiB per engine) for swapped
                         KV pages; a preemption past the budget
                         degrades to the re-prefill path instead of
                         growing host memory unboundedly
  ckpt_verify            legacy host checkpoint path (io.py): write a
                         CHECKPOINT_DIGESTS manifest on save_vars and
                         verify it before load_vars, sharing the mesh
                         path's verification story (CheckpointCorrupt-
                         Error naming the offending var + file)
  ckpt_async_workers     background writer threads per AsyncSharded-
                         Saver (checkpoint/sharded.py): file I/O,
                         digests and generation rotation overlap the
                         next training steps
  mesh_shape             MeshConfig.from_flags axis spec, e.g.
                         'dp=2,tp=2' ('' = pure data parallelism over
                         every local device)
  perf_peak_tflops       peak dense bf16 TFLOP/s that
                         obs/perf.device_peak_flops returns, the
                         denominator of an MFU (0 = the exact-
                         device_kind table in obs/perf.py, which knows
                         TPUs only and raises on any other device)
  slo_rules              declarative SLO rule list for obs/slo.py —
                         inline JSON (list of {name, metric, kind,
                         threshold[, min_count]}) or @/path/rules.json
                         ('' = no watchdog). Breaches emit slo.breach
                         trace events + the slo.breaches counter
  slo_check_secs         SLOWatchdog evaluation period in seconds
  online_poll_secs       ParamSubscriber (paddle_tpu/online/) version-
                         poll period in seconds — how often serving
                         asks its pservers for the published param
                         version between refreshes
  online_pull_timeout    seconds one refresh (version poll + shard
                         pulls + verify + stage) may take before it is
                         abandoned; the previously installed verified
                         version keeps serving
  sup_healthy_secs       Supervisor (distributed/supervisor.py): a role
                         that stayed up this long before dying gets its
                         restart BUDGET (and backoff exponent) reset —
                         a replica that crashes once a day is not a
                         crash loop. Lifetime restart counts (and the
                         incarnation fence they feed) are unaffected
  fleet_poll_secs        FleetRouter (serving/fleet.py) stream-pump
                         period: dispatch held requests + SRV_POLL
                         progress of every in-flight stream
  fleet_probe_secs       FleetRouter control period: SRV_HEALTH probe
                         of every replica + admission-rule evaluation +
                         autoscaler tick
  fleet_probe_fails      consecutive failed probes before a quiet
                         replica (no in-flight streams to trip the
                         pump) is declared dead; a failed poll/submit
                         kills it immediately
  fleet_max_hold         FleetRouter hold-queue bound — submissions
                         past this raise OverloadError regardless of
                         the admission rules
  fleet_shed_consecutive control periods a breached admission rule must
                         persist before the router starts shedding
                         (typed OverloadError on submit)
  fleet_admission_rules  obs/slo.py rule list (same format as
                         slo_rules) evaluated against the router's OWN
                         fleet.* snapshot as the admission-control
                         trigger; '' = the built-in fleet.queue_depth
                         gauge_max rule at fleet_max_hold / 2
  fleet_deploy_timeout   seconds rolling_deploy() may spend per replica
                         on drain + refresh + health-check before the
                         deploy aborts (the replica is un-drained)
  fleet_connect_timeout  cap (seconds) on the TCP connect step of one
                         router->replica call; the effective connect
                         timeout is min(per-call timeout, this) so a
                         short probe call can never spend longer
                         connecting than it was given overall
  fleet_probe_timeout    SRV_HEALTH probe RPC timeout (seconds) on the
                         router's DEDICATED per-replica probe
                         connection — deliberately far below
                         call_timeout so one stalled replica delays the
                         probe loop by at most this, not 10s
  fleet_progress_timeout_secs  gray-failure watchdog (serving/fleet.py):
                         a dispatched stream with no new token for this
                         long — or a router->replica RPC in flight this
                         long — gray-marks the replica and fails its
                         streams over through the re-prefill path
                         (bit-exact by greedy determinism). 0 = off
  fleet_hedge_ms         hedged dispatch: a stream with no first token
                         this many ms after dispatch is duplicated to a
                         second replica; first token wins, the loser is
                         SRV_CANCELled. Greedy determinism makes both
                         streams identical, so hedging can never change
                         output. 0 = off
  fleet_gray_probes      clean (in-time) SRV_HEALTH probes a gray-marked
                         replica must answer consecutively before it
                         rejoins dispatch (the half-open probation
                         length); a slow or failed probe resets the
                         count
  fleet_cache_shed_budget  cross-replica retries a stream that FAILED
                         with CacheExhaustedError gets (the router
                         requeues it onto a cooler replica) before the
                         failure is final — bounds the livelock when
                         the whole fleet is saturated; counted in
                         fleet.cache_sheds
  fleet_prefill_endpoints  disaggregated serving (serving/disagg.py):
                         comma-separated ReplicaServer endpoints that
                         form the PREFILL tier. When set, the router
                         routes each stream's prefill to this tier and
                         the computed KV pages are shipped over the
                         wire (SRV_PAGES) to the decode replica that
                         owns the stream; '' (default) keeps today's
                         colocated path
  disagg_ship_timeout    seconds one page ship (SRV_PAGE_FETCH prefill
                         + SRV_PAGES transfer + install) may take on
                         the decode replica before it gives up and
                         re-prefills locally (bit-exact by greedy
                         determinism)
  fleet_prefix_affinity  weight of the prefix-affinity term in the
                         router's dispatch score: the fraction of a
                         request's hash-chain prefix already resident
                         on a replica (per the fleet-wide prefix
                         directory) is subtracted from its load score
                         scaled by this, so shared-prefix requests
                         land where the pages live. 0 disables the
                         term
  spec_k                 speculative decoding (serving/speculative.py):
                         draft proposals per verify pass (the CEILING —
                         the predictor adapts k per slot between 1 and
                         this from the rolling accept rate; 0 disables
                         speculation)
  spec_draft_layers      self-draft depth: the draft model is the
                         target truncated to its first N transformer
                         blocks (same weights, zero extra weight HBM);
                         ignored when an explicit draft program is
                         given
  wire_binary_meta       frame the wire meta header in the compact
                         binary codec (wire version 3) instead of JSON
                         — negotiated per connection: a sender
                         advertises in its JSON meta, and only
                         upgrades after the peer has proven it speaks
                         v3, so old peers keep working (PERF round 10:
                         the JSON header is the 320×256B row's
                         remaining 2×)
"""
from __future__ import annotations

import os

__all__ = ['set_flags', 'get_flag', 'get_flags']

_DEFAULTS = {
    'check_nan_inf': False,
    'benchmark': False,
    'eager_delete_scope': True,
    'fraction_of_gpu_memory_to_use': 0.92,
    'init_allocated_mem': False,
    'use_pinned_memory': True,
    'use_pallas_fused_ops': False,
    'use_flash_attention': True,
    'pallas_interpret': False,
    # under AMP, round fp32-parameter gradients to bf16 at the grad-op
    # boundary: dW kernels write half the bytes and optimizer updates
    # read half — master weights and optimizer state stay fp32, so the
    # single rounding matches the standard bf16-grad training recipe
    # (Megatron-style). Off by default: exact-fp32 grad parity tests
    # rely on the precise path.
    'amp_bf16_param_grads': False,
    # mul (FC matmul) with one contracted dim on a batched input:
    # contract via 3D dot_general on the ORIGINAL shape instead of
    # flattening to 2D first, so the vjp-derived dW is a batch-dims
    # contraction over the un-flattened activation (measured faster on
    # the bench transformer of an earlier machine; not measured on this
    # one). Off = the reshape-to-2D formulation.
    'mul_dotgen': True,
    # flash-attention kernel block overrides (0 = use the tuned table
    # in pallas/flash_attention.py:_block_sizes)
    'flash_block_q': 0,
    'flash_block_k': 0,
    # seconds of trainer silence before a pserver declares it dead and
    # retires it from sync rounds (reference FLAGS_rpc_deadline,
    # operators/distributed/rpc_client.cc — applied server-side here
    # where the round state lives)
    'rpc_deadline': 180.0,
    # resilience layer (distributed/resilience.py): declarative fault
    # injection plan ('' = none; JSON, file path, or "seed:N")
    'fault_plan': '',
    # shared exponential-backoff RetryPolicy for the reconnecting RPC
    # clients (PSClient / MasterClient)
    'rpc_max_retries': 5,
    'rpc_retry_backoff': 0.05,
    'rpc_retry_max_backoff': 2.0,
    'rpc_reconnect_secs': 3.0,
    # per-trainer replay-dedup window on the ParameterService: replayed
    # SEND_VAR/BATCH_BARRIER/CHECKPOINT requests inside the window are
    # acked without re-applying
    'rpc_dedup_window': 512,
    # Trainer.train fault handling: step re-runs on retryable RPC
    # failure before escalating, and checkpoint rollbacks on fatal
    # failure before giving up
    'trainer_step_retries': 2,
    'trainer_max_rollbacks': 2,
    # elastic recovery (distributed/param_service.py, supervisor.py):
    # logical restart counter for THIS trainer process — the supervisor
    # sets it to the restart count; pservers fence lower values and
    # rejoin higher ones
    'trainer_incarnation': 0,
    # pserver durability: path of the atomic state snapshot ('' = no
    # durability); the mutation journal lives at <path>.journal
    'ps_state_path': '',
    # rounds between pserver snapshots (sync mode; async snapshots on a
    # send count instead)
    'ps_snapshot_every': 1,
    # pserver gradient integrity guard: reject non-finite SEND_VAR
    # payloads with a retryable error before they reach the journal or
    # the optimizer (wire bit-flips carry a valid CRC when the fault is
    # upstream of framing — this is the numeric backstop)
    'ps_check_grad_finite': True,
    # socket read deadline for the RPC clients: silence from a
    # connected peer for this long fails the attempt (retryable)
    # instead of hanging the trainer forever
    'rpc_read_deadline': 120.0,
    # pipelined transport (distributed/rpc.py *_async APIs): how many
    # unacked requests may ride one connection before submit blocks;
    # every unacked request is replayed in seq order after a transport
    # failure (the server dedup window makes that at-most-once)
    'rpc_inflight_window': 32,
    # small-tensor coalescing: dense gradients up to rpc_batch_bytes
    # each are packed into one SEND_VARS frame per endpoint (one CRC +
    # one header + one reply for dozens of BN scales/biases); a frame
    # flushes at rpc_batch_max_bytes total payload or
    # rpc_batch_max_vars entries. rpc_batch_bytes=0 turns batching off.
    'rpc_batch_bytes': 65536,
    'rpc_batch_max_bytes': 1 << 20,
    'rpc_batch_max_vars': 64,
    # Trainer numeric-anomaly guard (trainer.py): 'none' | 'rollback' |
    # 'fatal'. When enabled, a fused isfinite reduction over
    # loss + gradients is fetched each step; an anomalous step is
    # skipped (never checkpointed), and after anomaly_skip_steps
    # consecutive anomalies the action escalates
    'anomaly_action': 'none',
    'anomaly_skip_steps': 1,
    # _merge denominator: False (default) averages over the ORIGINAL
    # num_trainers (dead trainers contribute zero — comparable to the
    # full-set run), True averages over the live set (constant
    # effective LR after a death)
    'ps_average_live': False,
    # store the Momentum velocity accumulator in bf16 (halves the
    # optimizer's dominant HBM stream; one rounding per step; master
    # params stay fp32). Off by default for exact-fp32 parity.
    'bf16_momentum': False,
    # serving engine (paddle_tpu/serving/): decode slot-pool size,
    # admission queue bound, idle worker poll interval
    'serving_slots': 8,
    'serving_max_queue': 256,
    'serving_idle_wait': 0.05,
    # paged KV cache (serving/paging.py): tokens per page, pool size in
    # pages (0 = auto: slots * pages_per_slot + the reserved null page),
    # and the chunked-prefill slice width in tokens
    'serving_page_tokens': 16,
    'serving_kv_pages': 0,
    'serving_prefill_chunk': 64,
    # preempt-first capacity (serving/preempt.py): what CacheExhausted
    # does to the lowest-tier longest-idle stream ('swap' pages to host
    # RAM, 'reprefill' from accumulated tokens, 'off' = legacy typed
    # shed), and the host-RAM budget (MiB) for swapped pages
    'serving_preempt_policy': 'swap',
    'serving_swap_host_mb': 64,
    # mesh-sharded serving (serving/mesh.py): MeshConfig axis spec for
    # the decode/prefill/verify programs ('tp=2', 'dp=1,tp=4'; '' =
    # single-chip, the pre-mesh path). The page pool shards its heads
    # axis over tp; axes that do not divide (heads % tp != 0) fall back
    # to replicated via fit_spec, never error.
    'serve_mesh_shape': '',
    # sharded checkpointing (paddle_tpu/checkpoint/): digest-verify the
    # legacy host save/load path, async writer pool size, and the
    # MeshConfig.from_flags axis spec ('dp=2,tp=2'; '' = pure dp)
    'ckpt_verify': False,
    'ckpt_async_workers': 2,
    'mesh_shape': '',
    # observability (paddle_tpu/obs/): JSONL export root ('' = off),
    # per-process lane label, and metric export cadence
    'obs_dir': '',
    'obs_role': '',
    'obs_flush_secs': 2.0,
    # perf observatory (obs/perf.py): peak dense bf16 TFLOP/s override
    # of device_peak_flops (0 = the device_kind table)
    'perf_peak_tflops': 0.0,
    # SLO watchdog (obs/slo.py): declarative rule list — inline JSON or
    # @/path/rules.json ('' = off); evaluation cadence in seconds.
    # Armed by serving.Engine.start() and lazily by the first
    # instrumented training step.
    'slo_rules': '',
    'slo_check_secs': 5.0,
    # online refresh (paddle_tpu/online/): subscriber version-poll
    # cadence, and the wall budget one refresh (poll + pull + verify +
    # stage) gets before it is abandoned in favor of the installed
    # version
    'online_poll_secs': 0.5,
    'online_pull_timeout': 30.0,
    'sup_healthy_secs': 300.0,
    'fleet_poll_secs': 0.01,
    'fleet_probe_secs': 0.25,
    'fleet_probe_fails': 2,
    'fleet_max_hold': 512,
    'fleet_shed_consecutive': 2,
    'fleet_admission_rules': '',
    'fleet_deploy_timeout': 120.0,
    'fleet_cache_shed_budget': 5,
    # disaggregated prefill/decode serving (serving/disagg.py): the
    # prefill-tier endpoints ('' = colocated), the per-ship wall budget
    # on the decode side before local re-prefill, and the weight of the
    # prefix-directory affinity term in dispatch scoring (0 = off)
    'fleet_prefill_endpoints': '',
    'disagg_ship_timeout': 15.0,
    'fleet_prefix_affinity': 0.5,
    # gray-failure tolerance (serving/fleet.py): connect-step cap and
    # the dedicated probe-connection timeout (both seconds), the
    # no-progress watchdog horizon (0 = off), the hedged-dispatch
    # trigger in ms (0 = off), and the half-open probation length in
    # clean probes before a gray-marked replica rejoins dispatch
    'fleet_connect_timeout': 2.0,
    'fleet_probe_timeout': 1.0,
    'fleet_progress_timeout_secs': 0.0,
    'fleet_hedge_ms': 0.0,
    'fleet_gray_probes': 3,
    # speculative decoding (serving/speculative.py): max draft
    # proposals per verify pass (adaptive k's ceiling; 0 = off), and
    # the self-draft truncation depth in transformer blocks
    'spec_k': 4,
    'spec_draft_layers': 1,
    # wire meta header codec (distributed/wire.py): binary (v3 frames,
    # negotiated per connection with JSON fallback for old peers)
    'wire_binary_meta': False,
    # batch_norm under data parallelism: compute statistics per device
    # (the reference's semantics — multi_devices_graph_pass.cc replicates
    # batch_norm per device, so stats are local and un-synced) instead of
    # the default cross-replica SyncBN that GSPMD derives from reducing
    # over the sharded batch. Local mode removes every per-step BN-stat
    # all-reduce from the compiled HLO (116 latency-bound collectives in
    # the n=8 ResNet-50 step); scale/bias grads are psum'd so they join
    # the one coalesced gradient all-reduce. Running means/variances
    # update from LOCAL stats and therefore diverge per device exactly as
    # the reference's per-device copies do (the addressable shard-0 copy
    # wins at save/fetch time). See COVERAGE.md "divergences".
    'bn_local_stats': False,
}

_FLAGS = dict(_DEFAULTS)


def _coerce(name, value):
    default = _DEFAULTS.get(name)
    if isinstance(default, bool):
        if isinstance(value, str):
            return value.lower() in ('1', 'true', 'yes', 'on')
        return bool(value)
    if isinstance(default, float):
        return float(value)
    if isinstance(default, int):
        return int(value)
    return value


def set_flags(flags):
    """set_flags({'FLAGS_check_nan_inf': True}) — with or without the
    FLAGS_ prefix. Unknown names are stored as-is (scripts set custom
    flags; the reference's gflags tolerates registration order too)."""
    for name, value in flags.items():
        key = name[len('FLAGS_'):] if name.startswith('FLAGS_') else name
        _FLAGS[key] = _coerce(key, value)


def get_flag(name, default=None):
    key = name[len('FLAGS_'):] if name.startswith('FLAGS_') else name
    return _FLAGS.get(key, default)


def get_flags(names=None):
    if names is None:
        return dict(_FLAGS)
    return {n: get_flag(n) for n in names}


def _bootstrap_from_env():
    """Read FLAGS_* env vars once at import (reference __bootstrap__)."""
    for key, value in os.environ.items():
        if key.startswith('FLAGS_'):
            set_flags({key: value})


_bootstrap_from_env()
