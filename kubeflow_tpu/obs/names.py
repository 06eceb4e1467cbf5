"""The single definition site for every exposition name the platform emits.

Dashboards, smoke assertions, the chaos harness, and external Prometheus
scrape configs all key off these strings — a typo'd or drifting name is a
silent outage of the signal it carried. ``kft lint``'s ``metric-registry``
pass enforces that no ``kft_*`` / ``kubeflow_tpu_*`` literal appears
anywhere else in the package: recorders and registrars must reference
these constants, so renames are single-line diffs and every name in the
exposition provably has exactly one owner.

Grouped by plane. ``*_PREFIX`` constants are the sanctioned dynamic-name
roots (engine scheduler/pager stats fan out per-key under them).
"""

from __future__ import annotations

# -- orchestrator (control plane) ------------------------------------- #

#: histogram — controller sync_all wall time
RECONCILE_SECONDS = "kft_reconcile_seconds"
#: gauge{phase} — jobs currently in the store by phase
JOBS_BY_PHASE = "kft_jobs"
#: counter{reason} — workers killed by the heartbeat supervisor
SUPERVISOR_KILLS_TOTAL = "kft_supervisor_kills_total"
#: counter — gang restarts triggered by worker failures
GANG_RESTARTS_TOTAL = "kft_gang_restarts_total"
#: counter{reason} — gangs requeued after losing placement
GANG_REQUEUES_TOTAL = "kft_gang_requeues_total"
#: counter{condition,reason} — jobs reaching a terminal condition
JOBS_FINISHED_TOTAL = "kft_jobs_finished_total"

# -- quota scheduler (sched/) ------------------------------------------ #

#: gauge{queue,generation} — nominal chip quota per ClusterQueue
QUEUE_NOMINAL_CHIPS = "kft_queue_nominal_chips"
#: gauge{queue,generation} — chips held beyond nominal (cohort-borrowed)
QUEUE_BORROWED_CHIPS = "kft_queue_borrowed_chips"
#: gauge{queue} — workloads waiting for quota admission
QUEUE_PENDING_WORKLOADS = "kft_queue_pending_workloads"
#: counter{reason} — workloads preempted by the quota scheduler
PREEMPTIONS_TOTAL = "kft_preemptions_total"
#: histogram{queue} — enqueue-to-admission wait
QUEUE_WAIT_SECONDS = "kft_queue_wait_seconds"

# -- chaos harness ------------------------------------------------------ #

#: counter{kind} — faults the chaos runner actually injected
CHAOS_INJECTED_TOTAL = "kft_chaos_injected_total"
#: histogram — fault-to-recovered wall time
RECOVERY_SECONDS = "kft_recovery_seconds"

# -- training ----------------------------------------------------------- #

#: counter — restores that walked past a corrupt/unreadable step
CHECKPOINT_FALLBACKS_TOTAL = "kft_checkpoint_fallbacks_total"
#: gauges — the hot-loop overlap split (train/prefetch.py, train/metrics.py)
TRAIN_DATA_STALL_MS = "kubeflow_tpu_train_data_stall_ms"
TRAIN_H2D_MS = "kubeflow_tpu_train_h2d_ms"
TRAIN_DEVICE_STEP_MS = "kubeflow_tpu_train_device_step_ms"
TRAIN_COMPILE_MS = "kubeflow_tpu_train_compile_ms"
TRAIN_STEPS_PER_SEC = "kubeflow_tpu_train_steps_per_sec"

# -- XLA programs (core/compcache.py) ----------------------------------- #

#: counter — programs this process built: compiled, or loaded from the
#: persistent compilation cache
XLA_PROGRAMS_TOTAL = "kubeflow_tpu_xla_programs_total"
#: counter — wall seconds spent building them (cache loads included)
XLA_COMPILE_SECONDS_TOTAL = "kubeflow_tpu_xla_compile_seconds_total"
#: counter — of those programs, how many the persistent cache supplied
XLA_CACHE_HITS_TOTAL = "kubeflow_tpu_xla_cache_hits_total"

# -- inference gateway (gateway/) --------------------------------------- #

#: counter{service,code} — requests answered at the edge, by HTTP status
GATEWAY_REQUESTS_TOTAL = "kft_gateway_requests_total"
#: histogram{service} — edge-observed request latency (activator queue
#: time included: the client experienced it)
GATEWAY_LATENCY_SECONDS = "kft_gateway_latency_seconds"
#: gauge{service} — requests parked in the activator FIFO right now
GATEWAY_QUEUE_DEPTH = "kft_gateway_queue_depth"
#: counter{service,reason} — requests shed at the edge
#: (rate_limit / inflight_cap / queue_full / activation_timeout / no_backend)
GATEWAY_SHED_TOTAL = "kft_gateway_shed_total"
#: counter{service} — transparent re-dispatches after a backend failure
GATEWAY_RETRIES_TOTAL = "kft_gateway_retries_total"
#: counter{service} — hedged second requests dispatched
GATEWAY_HEDGES_TOTAL = "kft_gateway_hedges_total"
#: counter{service} — requests routed by prefix/session affinity
GATEWAY_AFFINITY_ROUTED_TOTAL = "kft_gateway_affinity_routed_total"
#: gauge{backend} — 1 while the backend's circuit breaker is open/half-open
GATEWAY_BREAKER_OPEN = "kft_gateway_breaker_open"
#: counter{backend} — closed→open breaker transitions
GATEWAY_BREAKER_OPENS_TOTAL = "kft_gateway_breaker_opens_total"
#: gauge{service} — backends currently eligible for selection
GATEWAY_BACKENDS_READY = "kft_gateway_backends_ready"
#: counter{service} — scale-from-zero kicks issued by the activator
GATEWAY_ACTIVATIONS_TOTAL = "kft_gateway_activations_total"
#: gauge{service} — activator FIFO depth under its autoscaler-facing name
#: (an autoscaler input: parked demand counts as concurrency, or
#: scale-from-zero never happens)
GATEWAY_ACTIVATOR_QUEUE_DEPTH = "kft_gateway_activator_queue_depth"
#: gauge{service} — 1 while a cold-episode scale-up kick is outstanding
GATEWAY_ACTIVATOR_COLD_EPISODE = "kft_gateway_activator_cold_episode"
#: counter{service,outcome} — mid-stream failovers: a decode stream whose
#: upstream died after bytes were committed, re-dispatched to a healthy
#: peer with the x-kft-resume-tokens contract (outcome: ok /
#: budget_exhausted / no_backend / failed)
GATEWAY_STREAM_RESUMES_TOTAL = "kft_gateway_stream_resumes_total"

# -- serving autoscaler (autoscale/) ------------------------------------ #

#: gauge{service} — the recommender's current desired replica count
AUTOSCALER_DESIRED_REPLICAS = "kft_autoscaler_desired_replicas"
#: gauge{service} — stable-window average observed concurrency
AUTOSCALER_STABLE_CONCURRENCY = "kft_autoscaler_stable_concurrency"
#: gauge{service} — panic-window average observed concurrency
AUTOSCALER_PANIC_CONCURRENCY = "kft_autoscaler_panic_concurrency"
#: gauge{service} — 1 while the service is in panic mode (no scale-down)
AUTOSCALER_PANIC_MODE = "kft_autoscaler_panic_mode"
#: counter{service,direction} — actuated replica-count changes (up/down)
AUTOSCALER_SCALE_EVENTS_TOTAL = "kft_autoscaler_scale_events_total"
#: counter{service} — prefix-KV entries moved between replicas after a
#: hash-ring remap (scale-up pull / scale-down evacuation)
AUTOSCALER_KV_TRANSFERS_TOTAL = "kft_autoscaler_kv_transfers_total"
#: gauge{service} — replicas a fleet currently runs (the actuated count,
#: as opposed to the recommender's desired count above); the loadgen
#: reporter reads its movement to time 1→N scale-up
FLEET_REPLICAS = "kft_fleet_replicas"

# -- load harness (loadgen/) --------------------------------------------- #

#: counter{tenant,outcome} — client-side verdict on every loadgen request
#: (completed_in_slo / completed_late / shed / error); the client-truth
#: complement of the gateway's server-side counters
LOADGEN_REQUESTS_TOTAL = "kft_loadgen_requests_total"

# -- serving ------------------------------------------------------------ #

#: gauge{model} — requests currently executing in the dataplane (the
#: load signal the gateway's least-outstanding balancer cross-checks)
SERVER_INFLIGHT = "kft_server_inflight"
#: gauge{model} — instances waiting in the batcher queue
SERVER_QUEUE_DEPTH = "kft_server_queue_depth"

#: counter{model} — model loads that raised (ModelMesh)
MODELMESH_LOAD_FAILURES_TOTAL = "kft_modelmesh_load_failures_total"
#: gauges{model} — batcher occupancy (shared registry + /metrics)
BATCHER_BATCHES = "kubeflow_tpu_batcher_batches"
BATCHER_INSTANCES = "kubeflow_tpu_batcher_instances"
BATCHER_MEAN_OCCUPANCY = "kubeflow_tpu_batcher_mean_occupancy"
#: gauge{model} — co-batched failures re-run per caller (offender isolation)
BATCHER_FAIL_ISOLATIONS = "kubeflow_tpu_batcher_fail_isolations"
#: dataplane request metrics (ModelServer /metrics exposition)
REQUESTS_TOTAL = "kubeflow_tpu_requests_total"
LATENCY_P50_MS = "kubeflow_tpu_latency_p50_ms"
LATENCY_P99_MS = "kubeflow_tpu_latency_p99_ms"
#: continuous-batching engine gauges; per-key stats fan out under the
#: prefixes (scheduler stats, paged-KV pool pressure)
ENGINE_ACTIVE_ROWS = "kubeflow_tpu_engine_active_rows"
ENGINE_PREFIX = "kubeflow_tpu_engine_"
ENGINE_KV_PREFIX = "kubeflow_tpu_engine_kv_"
#: pipelined-decode overlap gauges (serve/engine.py `overlap` dict):
#: host time between chunk dispatches — the dead bus time the pipeline
#: exists to remove
ENGINE_DECODE_GAP_MS = "kft_engine_decode_gap_ms"
#: token-drain D2H sync time per chunk (overlapped by the next chunk)
ENGINE_D2H_DRAIN_MS = "kft_engine_d2h_drain_ms"
#: counter — carry epoch re-uploads; grows with admissions/retirements,
#: NOT with chunks (steady-state decode performs zero per-chunk H2D)
ENGINE_CARRY_UPLOADS_TOTAL = "kft_engine_carry_uploads_total"
#: EWMA occupied-row fraction at chunk dispatch
ENGINE_SLOT_OCCUPANCY = "kft_engine_slot_occupancy"
#: prefix-cache effectiveness (the signal the gateway's prefix affinity
#: steers by): cumulative hits / KV tokens reused, live entry count and
#: stored-token occupancy
ENGINE_PREFIX_HITS_TOTAL = "kft_engine_prefix_hits_total"
ENGINE_PREFIX_TOKENS_REUSED_TOTAL = "kft_engine_prefix_tokens_reused_total"
ENGINE_PREFIX_ENTRIES = "kft_engine_prefix_entries"
ENGINE_PREFIX_TOKENS_STORED = "kft_engine_prefix_tokens_stored"
#: cross-replica prefix-KV transfer (serve/server.py peer endpoints):
#: entries imported from / exported to a peer replica — a hit served
#: from an imported entry is KV that was never re-prefilled here
ENGINE_PREFIX_IMPORTED_TOTAL = "kft_engine_prefix_imported_total"
ENGINE_PREFIX_EXPORTED_TOTAL = "kft_engine_prefix_exported_total"
#: speculative decoding (serve/speculative.py): draft tokens proposed /
#: accepted by the in-graph verify, and the EWMA acceptance ratio — the
#: tokens-per-forward multiplier prompt-lookup is buying
ENGINE_SPEC_PROPOSED_TOTAL = "kft_engine_spec_proposed_total"
ENGINE_SPEC_ACCEPTED_TOTAL = "kft_engine_spec_accepted_total"
ENGINE_SPEC_ACCEPTANCE = "kft_engine_spec_acceptance"
#: int8 KV-cache quantization (ops/paged_attention.py): EWMA of the
#: mean-abs relative quantization error measured at prefill writes
ENGINE_KV_QUANT_ERROR = "kft_engine_kv_quant_error"
#: disaggregated prefill/decode (serve/engine.py prefill_span / inject):
#: counter{model,direction} — bytes of per-request KV spans shipped over
#: the wire (direction: export on the prefill replica, import on decode)
ENGINE_KV_SHIP_BYTES_TOTAL = "kft_engine_kv_ship_bytes_total"
#: histogram{model} — one KV-span ship leg end to end, milliseconds
#: (decode-side: peer prefill RPC + decode + inject-validate)
ENGINE_KV_SHIP_MS = "kft_engine_kv_ship_ms"
#: host-RAM KV tier (serve/kv_tier.py): gauge{model} — encoded KV bytes
#: resident in the bounded host pool
ENGINE_KV_OFFLOAD_BYTES = "kft_engine_kv_offload_bytes"
#: gauge{model} — swapped-out session rows resident in the host tier
ENGINE_KV_OFFLOAD_RESIDENT_ROWS = "kft_engine_kv_offload_resident_rows"

# -- serving SRE layer (serve/deadline.py, serve/watchdog.py) ------------ #

#: counter{stage} — requests retired because their end-to-end deadline
#: expired (admission / queued / decoding / wait / batch_queue)
ENGINE_DEADLINE_EXPIRED_TOTAL = "kft_engine_deadline_expired_total"
#: counter{reason} — requests shed by deadline-aware admission control
#: (deadline_unmeetable / priority_evict) BEFORE costing a decode slot
ENGINE_ADMISSION_SHED_TOTAL = "kft_engine_admission_shed_total"
#: counter{model,reason} — engine watchdog trips (wedged / loop_dead /
#: fatal); each trip flips readiness and triggers a supervised restart
ENGINE_WATCHDOG_TRIPS_TOTAL = "kft_engine_watchdog_trips_total"
#: counter{model} — supervised engine restarts (device state rebuilt)
ENGINE_RESTARTS_TOTAL = "kft_engine_restarts_total"
#: counter{model} — requests admitted with a committed-token resume
#: prefix (the engine half of the gateway's mid-stream failover)
ENGINE_RESUME_ADMITS_TOTAL = "kft_engine_resume_admits_total"

# -- request tracing (obs/trace.py) -------------------------------------- #

#: histogram{model} — server-side time-to-first-token of traced requests,
#: milliseconds (engine enqueue → first pushed token)
SERVER_TTFT_MS = "kft_server_ttft_ms"
#: histogram{model} — server-side mean time-per-output-token after the
#: first, milliseconds (the steady-state decode pace SLOs bind to)
SERVER_TPOT_MS = "kft_server_tpot_ms"
#: counter{decision} — tail-sampler verdicts on finished traces
#: (error / slow / sampled / dropped); error+slow+sampled are retained
TRACE_SAMPLER_DECISIONS_TOTAL = "kft_trace_sampler_decisions_total"
