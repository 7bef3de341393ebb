"""Global flag registry: ``set_flags`` / ``get_flags``.

Reference: platform/flags.cc:44 (gflags-backed registry) +
fluid/framework.py set_flags/get_flags.  Flags are initialized from
``FLAGS_*`` environment variables at import, like gflags does.
"""
from __future__ import annotations

import os
from typing import Dict, Iterable, Union

from . import watch as _watch

__all__ = ["set_flags", "get_flags", "register_flag", "all_flags"]

_FLAGS: Dict[str, object] = {}
_DEFS: Dict[str, tuple] = {}  # name -> (type, default, help)


def register_flag(name: str, default, help_str: str = ""):
    typ = type(default)
    _DEFS[name] = (typ, default, help_str)
    env = os.environ.get(name)
    if env is not None:
        if typ is bool:
            _FLAGS[name] = env.lower() in ("1", "true", "yes", "on")
        else:
            _FLAGS[name] = typ(env)
    else:
        _FLAGS[name] = default


def _coerce(typ, value):
    if typ is bool and isinstance(value, str):
        # bool('0') is True; parse strings like the env path does
        return value.lower() in ("1", "true", "yes", "on")
    return typ(value)


def set_flags(flags: Dict[str, object]):
    """reference fluid.set_flags({'FLAGS_check_nan_inf': 1})."""
    for name, value in flags.items():
        if name not in _DEFS:
            raise ValueError(f"unknown flag {name!r}; known: "
                             f"{sorted(_DEFS)}")
        _FLAGS[name] = _coerce(_DEFS[name][0], value)


def get_flags(flags: Union[str, Iterable[str]]):
    """reference fluid.get_flags: str -> value, list -> dict."""
    if isinstance(flags, str):
        if flags not in _FLAGS:
            raise ValueError(f"unknown flag {flags!r}")
        return {flags: flag_value(flags)}
    return {f: get_flags(f)[f] for f in flags}


def all_flags() -> Dict[str, object]:
    """Every registered flag's current value (the ``/statusz``
    introspection payload: an operator diagnosing a live server needs
    the flags it actually runs with, not the defaults)."""
    return {name: _FLAGS.get(name, _DEFS[name][1]) for name in _DEFS}


def flag_value(name: str):
    """Internal fast-path accessor; the one reader of a flag's value
    (a thread that traces a Program has its reads recorded:
    ``watch.py``)."""
    value = _FLAGS.get(name, _DEFS.get(name, (None, None))[1])
    if _watch.active:
        seen = _watch.current()
        if seen is not None:
            seen.flags[name] = value
    return value


# -- the flag set (reference platform/flags.cc + nan_inf_utils) -------------
register_flag("FLAGS_check_nan_inf", False,
              "run ops eagerly and raise, naming the op, on the first "
              "non-finite output (framework/details/nan_inf_utils)")
register_flag("FLAGS_benchmark", False,
              "sync and print per-run wall time in Executor.run")
register_flag("FLAGS_eager_delete_tensor_gb", 0.0,
              "GC threshold (advisory: XLA owns buffer lifetime)")
register_flag("FLAGS_fraction_of_gpu_memory_to_use", 0.92,
              "accelerator memory fraction (advisory under XLA)")
register_flag("FLAGS_allocator_strategy", "auto_growth",
              "allocator strategy (advisory under XLA)")
register_flag("FLAGS_cudnn_deterministic", False,
              "deterministic kernels (XLA is deterministic by default)")
register_flag("FLAGS_paddle_num_threads", 1,
              "host threads per op (advisory)")
register_flag("FLAGS_fault_inject", "",
              "deterministic fault-injection spec: comma-separated "
              "site:kind@N / site:kind@N+ / site:kind~p entries "
              "(paddle_tpu/fault.py; e.g. 'ckpt_write:torn@2,loss:nan@5')")
register_flag("FLAGS_fault_seed", 0,
              "seed for probabilistic (~p) fault-injection triggers")
register_flag("FLAGS_checkpoint_retries", 2,
              "retry a failed checkpoint write up to N more times "
              "(exponential backoff) before giving up")
register_flag("FLAGS_checkpoint_retry_backoff_s", 0.05,
              "base backoff (seconds) between checkpoint write retries")
register_flag("FLAGS_guard_resolve_interval", 64,
              "deferred non-finite guard: resolve the pending on-device "
              "ok-verdict ring at most every N guarded steps when nothing "
              "else (a fetch read, a checkpoint, close) forces it; "
              "1 restores the synchronous per-step host check, 0 defers "
              "indefinitely (fetch/checkpoint/close only)")
register_flag("FLAGS_feed_double_buffer", True,
              "stage numpy Executor.run feeds onto the device through a "
              "2-deep device_put ring so the H2D copy of step N+1 "
              "overlaps the compute of step N")
register_flag("FLAGS_telemetry", True,
              "master switch for paddle_tpu/telemetry.py: 0 turns spans, "
              "typed metrics, and every file exporter into constant-time "
              "no-ops (one dict lookup on the hot path)")
register_flag("FLAGS_metrics_dir", "",
              "directory for the telemetry file exporters (metrics.prom "
              "Prometheus textfile, events.jsonl event log, heartbeat.json "
              "health file, trace.json Perfetto trace); empty disables "
              "all file output")
register_flag("FLAGS_metrics_interval", 10.0,
              "seconds between periodic telemetry flushes (Prometheus "
              "textfile + heartbeat + trace), checked on the hot path "
              "with one monotonic read")
register_flag("FLAGS_trace_buffer_size", 4096,
              "capacity of the completed-span ring buffer "
              "(paddle_tpu/telemetry.py); oldest spans drop first")
register_flag("FLAGS_serving_max_batch", 8,
              "serving engine: largest micro-batch (= largest padding "
              "bucket) the dynamic batcher forms; buckets are the powers "
              "of two up to this value (paddle_tpu/serving)")
register_flag("FLAGS_serving_max_delay_ms", 5.0,
              "serving engine: longest a worker holds a partial batch "
              "open waiting for more requests before dispatching it "
              "padded (the latency half of the batching policy)")
register_flag("FLAGS_serving_queue_cap", 256,
              "serving engine: bounded admission queue; submit() on a "
              "full queue sheds with an explicit OverloadedError instead "
              "of queuing unbounded latency")
register_flag("FLAGS_serving_deadline_ms", 1000.0,
              "serving engine: requests that waited longer than this in "
              "the queue are shed (OverloadedError) when a worker picks "
              "them up — bounds admission-latency p99 under overload")
register_flag("FLAGS_serving_workers", 2,
              "serving engine: predictor-pool size (clone()d predictors "
              "sharing device weights, one dispatch thread each)")
register_flag("FLAGS_serving_decode_slots", 8,
              "generation engine: decode-slot grid size — the whole "
              "grid runs every decode iteration, finished sequences "
              "free their slot to the next queued request immediately "
              "(paddle_tpu/serving/generation.py)")
register_flag("FLAGS_serving_max_seq_len", 256,
              "generation engine: per-slot KV-cache sequence capacity "
              "(prompt + generated tokens); the cache HBM footprint is "
              "slots * layers * 2 * n_kv_heads * max_seq_len * head_dim "
              "* 4 bytes")
register_flag("FLAGS_serving_prefill_buckets", "",
              "comma-separated prefill sequence-length buckets "
              "(prompts pad up to the smallest fitting bucket, one "
              "compiled executable per bucket); empty = powers of two "
              "from 8 up to FLAGS_serving_max_seq_len")
register_flag("FLAGS_serving_max_new_tokens", 64,
              "generation engine: default per-request cap on generated "
              "tokens (a request's own max_new_tokens wins; a budget "
              "beyond the cache capacity left after the prompt decodes "
              "until the slot cache fills and finishes 'cache_full')")
register_flag("FLAGS_serving_kv_page_tokens", 16,
              "generation engine's block-paged KV cache (fixed-size "
              "pages + per-slot block tables, so concurrency is bounded "
              "by LIVE tokens): tokens per page (power of two dividing "
              "FLAGS_serving_max_seq_len); smaller pages waste less on "
              "short sequences but deepen the per-slot block table "
              "(paddle_tpu/serving/kv_cache.py)")
register_flag("FLAGS_serving_kv_pages", 0,
              "paged KV cache: physical pages in the per-layer pool "
              "(page 0 is the reserved trash page garbage writes are "
              "redirected to); 0 = auto-size to every slot's worst case "
              "(slots * max_seq_len / page_tokens + 1) — the pool HBM "
              "footprint is pages * layers * 2 * n_kv_heads * "
              "page_tokens * head_dim * 4 bytes")
register_flag("FLAGS_serving_prefill_chunk", 0,
              "paged generation: feed long prompts in slices of this "
              "many tokens, one slice per scheduler iteration "
              "interleaved with decode steps (SarathiServe-style "
              "chunked prefill), so a long prompt no longer stalls the "
              "whole grid's inter-token latency; 0 = whole-prompt "
              "prefill")
register_flag("FLAGS_serving_prefix_reuse", True,
              "paged generation: hash page-aligned prompt-prefix chunks "
              "(system prompts, few-shot headers) and map index hits "
              "into new slots copy-on-write — their prefill is skipped "
              "entirely and the pages are shared refcounted until every "
              "referencing slot finishes; 0 disables the prefix index")
register_flag("FLAGS_serving_speculate", False,
              "paged generation: speculative decoding — a prompt-lookup "
              "n-gram drafter proposes up to FLAGS_serving_spec_tokens "
              "tokens per slot per scheduler iteration from the "
              "sequence's OWN prompt+generated history (no second "
              "model), a single chunk-shaped verify program scores the "
              "draft against the paged cache, and the longest "
              "argmax-agreeing prefix (plus the one bonus token) is "
              "accepted — bit-exact vs plain greedy decode, token-for-"
              "token and logit-for-logit.  Rejected draft tokens roll "
              "their provisionally-written KV pages back through the "
              "refcounted pool")
register_flag("FLAGS_serving_spec_tokens", 4,
              "speculative decoding: maximum draft tokens proposed per "
              "slot per verify (the verify chunk scores draft+1 rows); "
              "larger drafts amortize more grid steps on repetitive "
              "text but waste verify compute when acceptance is low")
register_flag("FLAGS_serving_spec_ngram", 3,
              "speculative decoding: longest n-gram suffix the prompt-"
              "lookup drafter matches against the sequence history "
              "(falls back to shorter n-grams down to 1; a slot with "
              "no match this iteration takes the plain one-token grid "
              "step)")
register_flag("FLAGS_serving_role", "both",
              "disaggregated serving role of this GenerationEngine / "
              "replica: 'both' (colocated prefill+decode, the default), "
              "'prefill' (runs paged prefill and exports each prompt's "
              "populated pages as a KVSegment, never occupies a decode "
              "slot), 'decode' (accepts segments via adopt()/POST "
              "/adopt and runs only the decode grid)")
register_flag("FLAGS_disagg_reprefill", False,
              "disaggregated routing: when the cache-holding decode "
              "replica dies mid-generation the router fails the "
              "request with the explicit 'affinity_lost' taxonomy by "
              "default (never a silent re-prefill); 1 lets the router "
              "restart the whole prefill->adopt pipeline once on "
              "surviving replicas instead")
register_flag("FLAGS_disagg_transport", "device",
              "in-process KV-segment handoff transport (DisaggPair "
              "default): 'device' = device-to-device jax.device_put "
              "between the engines' (sub-)meshes, zero host copy; "
              "'bytes' = serialize through the KVSegment wire codec — "
              "the exact bytes POST /adopt carries, i.e. what a "
              "cross-host transport pays")
register_flag("FLAGS_trace_sample", 1.0,
              "head-sampling rate for serving request traces: fraction "
              "of requests (0..1, deterministic every-Nth spacing) that "
              "record full serving/admit..respond span trees; unsampled "
              "requests keep phase timings only.  Independent of the "
              "always-keep-slowest-N tail capture (FLAGS_trace_tail_keep)")
register_flag("FLAGS_trace_tail_keep", 8,
              "tail capture: always keep the N slowest request traces "
              "regardless of head sampling (the /tracez 'slowest' list "
              "— the requests worth asking 'why was this slow' about)")
register_flag("FLAGS_tracez_recent", 32,
              "how many recent head-sampled request traces /tracez "
              "retains (bounded ring; oldest drop first)")
register_flag("FLAGS_histogram_buckets", "",
              "comma-separated upper bounds (ms) overriding the default "
              "telemetry histogram buckets for histograms created "
              "without explicit buckets; empty keeps DEFAULT_BUCKETS_MS")
register_flag("FLAGS_device_peak_flops", 0.0,
              "per-chip peak TFLOP/s override for the costmodel peak "
              "table (paddle_tpu/costmodel.py); 0 = auto from "
              "device_kind (an unknown device_kind has no peak)")
register_flag("FLAGS_device_peak_bw", 0.0,
              "per-chip peak HBM GB/s override for the costmodel peak "
              "table; 0 = auto from device_kind")
register_flag("FLAGS_hbm_sample_interval", 0.25,
              "seconds between HBM live-buffer samples taken by the "
              "observatory sampling thread (hbm_live_bytes / "
              "hbm_peak_bytes gauges + the Perfetto counter track); "
              "0 disables the sampler")
register_flag("FLAGS_profilez_sec", 2.0,
              "default duration (seconds) of an on-demand profiler "
              "capture (GET /profilez, TrainGuard SIGUSR2); capped at "
              "60s per capture")
register_flag("FLAGS_serving_mesh", "",
              "sharded-serving topology spec for ReplicaGroupEngine "
              "(paddle_tpu/serving/sharded.py): 'dp=4,mp=2' makes 4 "
              "replica groups of 2-device weight-sharded sub-meshes; "
              "dp multiplies throughput, mp divides a too-big model's "
              "dense weights across a group (ep shards what mp "
              "doesn't divide, e.g. expert tables).  Explicit "
              "constructor kwargs win over the flag; empty = "
              "unsharded")
register_flag("FLAGS_serving_group_degraded_after", 3,
              "sharded serving: a replica group (engine worker) whose "
              "batches failed this many times CONSECUTIVELY reports "
              "status 'degraded' in /healthz and /statusz (it keeps "
              "pulling work — one success resets the streak); the "
              "engine-level status degrades with it")
register_flag("FLAGS_serving_access_log", "",
              "path of the serving JSONL access log (one line per HTTP "
              "request: trace_id, status, per-phase latency breakdown); "
              "empty defaults to <FLAGS_metrics_dir>/access.jsonl when a "
              "metrics dir is set, else disabled")
register_flag("FLAGS_serving_bisect", True,
              "serving engine: when a multi-request batch fails, "
              "recursively split-and-retry it to isolate the poisoned "
              "request(s) — exactly the offending requests error, every "
              "other rider is served bit-exact (cost bounded at "
              "(log2(batch)+1) re-dispatches of the original rows); "
              "0 restores fail-the-whole-batch")
register_flag("FLAGS_serving_poison_value", "",
              "chaos/testing hook: a float sentinel; any batch (or "
              "generation prompt) containing a feed value exactly equal "
              "to it raises PoisonedInput at execution — a deterministic "
              "stand-in for an input that crashes the model kernel, "
              "used by the bisection fault matrix and tools/chaos.py; "
              "empty disables (the serve path pays nothing)")
register_flag("FLAGS_embedding_shards", 0,
              "recommender serving tier (paddle_tpu/serving/embedding.py):"
              " number of row shards the embedding table splits into "
              "across the ep device ring (shards cycle the local devices "
              "when they outnumber them, so a larger-than-HBM table "
              "still places).  0 = one shard per local device")
register_flag("FLAGS_embedding_placement", "mod",
              "embedding tier row-placement rule: 'mod' stripes row r "
              "onto shard r %% shards (uniform under any id "
              "distribution — the default), 'range' gives shard s the "
              "contiguous block [s*ceil(vocab/shards), ...) (locality "
              "for range-partitioned id spaces).  Both reassemble "
              "bit-exact vs the unsharded table")
register_flag("FLAGS_embedding_cache_rows", 4096,
              "embedding tier hot-row cache capacity in ROWS (refcounted"
              " LRU fronting the shard gathers, as kv_cache.PrefixIndex): a "
              "hit skips the device gather for that id; eviction only "
              "takes rows no in-flight lookup has pinned.  0 disables "
              "the cache (every id gathers)")
register_flag("FLAGS_serving_recsys_max_batch", 64,
              "default ServingEngine max_batch for --recsys replicas "
              "(the many-small-requests regime wants a much larger "
              "fan-in than the dense default FLAGS_serving_max_batch): "
              "thousands of 1-row lookup-dominated requests amortize "
              "into few large gathers")
register_flag("FLAGS_serving_recsys_fanin", True,
              "recsys replicas batch over the fan-in bucket ladder "
              "(batcher.fanin_bucket_sizes: dense powers of two up to 8,"
              " then sparse 4x jumps to max_batch) instead of the full "
              "power-of-two ladder — fewer mid-ladder executables where "
              "tiny-request traffic never lands; 0 restores pow2 "
              "buckets")
register_flag("FLAGS_serving_worker_stuck_ms", 10000.0,
              "serving engine: a dispatch worker whose current batch has "
              "been executing longer than this reports status 'stuck' "
              "(with stuck_ms) in worker_health()/ /healthz — the "
              "engine-level status degrades so the router stops "
              "preferring the replica; 0 disables the watchdog")
register_flag("FLAGS_router_forward_timeout_ms", 0.0,
              "fleet router: socket timeout for one replica forward — a "
              "hung replica costs at most this per attempt (strikes its "
              "health, retries once on an alternate, 504 when none); "
              "a request's remaining deadline budget tightens it "
              "further; 0 falls back to the router's request_timeout_s "
              "(default 30s)")
register_flag("FLAGS_router_default_deadline_ms", 0.0,
              "fleet router: end-to-end deadline budget (ms) MINTED into "
              "X-PaddleTPU-Deadline-Ms for requests that arrive without "
              "one; the budget decrements across hops and replica "
              "admission sheds hopeless requests at the queue; 0 mints "
              "nothing (client-supplied headers still propagate)")
register_flag("FLAGS_fleet_liveness_timeout_ms", 5000.0,
              "fleet supervisor: a replica whose PID is alive but whose "
              "/healthz has not answered for this long after previously "
              "answering (SIGSTOP'd / wedged, invisible to exit-code "
              "monitoring) is SIGKILLed and respawned through the crash "
              "path (fleet_hung_kills); 0 disables the liveness "
              "watchdog")
register_flag("FLAGS_router_health_interval_ms", 200.0,
              "fleet router: cadence of the background /healthz poll "
              "against every registered replica (queue depth, inflight "
              "rows, ready flag feed the least-loaded routing score)")
register_flag("FLAGS_router_health_stale_ms", 2000.0,
              "fleet router: a replica whose last successful health "
              "poll is older than this is DEPRIORITIZED (routed to only "
              "when no fresh replica exists) — a silent replica must "
              "not keep winning the least-loaded comparison on frozen "
              "numbers")
register_flag("FLAGS_router_eject_after", 2,
              "fleet router: consecutive failed health polls before a "
              "replica is EJECTED from the routing set entirely (it "
              "rejoins on the first successful poll reporting ready)")
register_flag("FLAGS_router_slo_p99_ms", 250.0,
              "fleet router: the served-latency SLO the autoscaling "
              "signal is derived from — fleet_wanted_replicas scales "
              "live replicas by max(p99/SLO, queue-depth pressure) "
              "(paddle_tpu/serving/router.py)")
register_flag("FLAGS_fleet_replicas", 2,
              "fleet supervisor: replica server processes to spawn "
              "(paddle_tpu/serving/fleet.py; each gets its own port, "
              "metrics dir, and PADDLE_TPU_REPLICA_ID env)")
register_flag("FLAGS_fleet_max_restarts", 3,
              "fleet supervisor: respawn a CRASHED replica up to N "
              "times (exponential backoff, PADDLE_TPU_RESTART_COUNT "
              "accounting); past the budget the replica stays down and "
              "fleet_replicas_live drops.  Rolling-restart respawns "
              "are planned exits and do not count")
register_flag("FLAGS_debug_lock_order", False,
              "runtime lock-order sanitizer (paddle_tpu/locksan.py): "
              "wrap every threading.Lock/RLock constructed after "
              "import in an order-recording shim, assert the observed "
              "per-thread acquisition graph stays acyclic, and record "
              "inversions in locksan.violations().  Debug/test only: "
              "costs a thread-local append per acquire plus a graph "
              "check on nested acquires; 0 (default) patches nothing "
              "and costs nothing")
register_flag("FLAGS_fleet_restart_backoff_ms", 200.0,
              "fleet supervisor: base crash-respawn backoff; doubles "
              "per consecutive crash of the same replica (capped at "
              "5s), resets after a healthy start")
register_flag("FLAGS_tsdb", True,
              "in-process time-series store (paddle_tpu/tsdb.py): the "
              "telemetry flush cadence records every counter/gauge and "
              "each histogram's count/p50/p99 as (ts, value) rings for "
              "windowed rate/delta/quantile queries — the layer the "
              "fleet observatory, burn-rate alerts, and the autoscale "
              "signal read.  0 disables recording (and the monitors go "
              "evidence-blind); FLAGS_telemetry=0 disables it too")
register_flag("FLAGS_tsdb_points", 512,
              "tsdb ring capacity per series: memory is hard-bounded "
              "at max_series x points x ~60 bytes per store.  At the "
              "default 10s FLAGS_metrics_interval cadence, 512 points "
              "is ~85 minutes of history")
register_flag("FLAGS_slo_availability_pct", 99.0,
              "availability objective the burn-rate monitor alerts "
              "against: the error budget is (100 - this)% of requests "
              "over the alerting windows (SRE-workbook multi-window "
              "burn rate; paddle_tpu/tsdb.py BurnRateMonitor)")
register_flag("FLAGS_slo_p99_ms", 0.0,
              "latency SLO threshold for the burn-rate monitor's p99 "
              "spec: the budget is 1% of requests above this many ms. "
              "0 inherits FLAGS_router_slo_p99_ms (one knob for the "
              "autoscale signal and the alert by default)")
register_flag("FLAGS_slo_fast_window_s", 60.0,
              "burn-rate FAST window: an alert needs this window's "
              "burn over threshold too (proves the problem is still "
              "happening), and clearing is judged on it alone (a "
              "recovered fleet clears in about one fast window)")
register_flag("FLAGS_slo_slow_window_s", 300.0,
              "burn-rate SLOW window: an alert needs this window's "
              "burn over threshold (proves the problem is real, not "
              "one bad scrape).  Must be longer than the fast window")
register_flag("FLAGS_slo_burn_threshold", 2.0,
              "burn-rate alert threshold: fire when BOTH windows burn "
              "error budget at >= this multiple of the sustainable "
              "rate (1.0 = exactly consuming the budget); clear with "
              "hysteresis when the fast window drops below half of it")
register_flag("FLAGS_router_federate", True,
              "fleet router: scrape every replica's /metrics on the "
              "health-poll cadence, keep per-replica windowed series "
              "in the router tsdb, and serve the fleet aggregate on "
              "GET /fleetz plus replica-labeled fleet_* series on the "
              "router's own /metrics.  0 = health polling only")
register_flag("FLAGS_swap_timeout_s", 30.0,
              "in-place weight swap: max seconds to quiesce at a "
              "drained-batch / decode-grid-step boundary before the "
              "swap gives up (serving keeps running on the old "
              "weights; paddle_tpu/serving/engine.py swap_weights)")
register_flag("FLAGS_canary_fraction", 0.25,
              "canary rollout: fraction of the fleet Router.canary "
              "hot-swaps to the new checkpoint and weights the "
              "traffic split by (bounded to [1, N-1] replicas; "
              "paddle_tpu/serving/router.py)")
register_flag("FLAGS_canary_soak_s", 60.0,
              "canary rollout: soak window.  A canary that survives "
              "this long without a per-version burn-rate alert (or a "
              "canary replica crash) promotes to the rest of the "
              "fleet; sustained burn before then auto-reverts")
register_flag("FLAGS_blackbox", True,
              "black-box flight recorder (paddle_tpu/blackbox.py): "
              "bounded in-memory rings of recent log events, metric "
              "snapshots, and per-request last words, dumped to "
              "<FLAGS_metrics_dir>/postmortem/<pid>-<reason>.json on "
              "fatal signals, uncaught scheduler exceptions, and "
              "explicit request.  0 = zero per-request work (one dict "
              "lookup, nothing recorded, no dumps); FLAGS_telemetry=0 "
              "disables it too")
register_flag("FLAGS_blackbox_events", 256,
              "flight recorder: capacity of the last-K event ring "
              "(mirrored telemetry log_event records); oldest drop "
              "first")
register_flag("FLAGS_blackbox_requests", 64,
              "flight recorder: max in-flight request last-words "
              "entries held at once; admissions past the cap are "
              "not recorded (counted in the ring's dropped field)")
register_flag("FLAGS_serving_check_outputs", False,
              "serving engine: reject batches whose outputs contain "
              "non-finite values (RequestFailed for the batch's rows) "
              "— the bad-checkpoint tripwire the canary burn-rate "
              "judge feeds on.  Off by default: costs one isfinite "
              "scan per batch on the serve path")
register_flag("FLAGS_usage", True,
              "per-tenant usage ledger (paddle_tpu/serving/usage.py): "
              "attribute every request's cost vector (requests, "
              "tokens, steps, flops, KV page-seconds, cache hits, "
              "sheds, failures) to its X-PaddleTPU-Tenant, exposed on "
              "/usagez and federated into /fleetz.  0 = zero "
              "per-request work (one dict lookup, no ledger, no "
              "per-tenant series); FLAGS_telemetry=0 disables the "
              "per-tenant latency/SLO series but the ledger still "
              "books counters")
register_flag("FLAGS_usage_top_k", 32,
              "usage ledger: space-saving heavy-hitter sketch width — "
              "at most this many tenants tracked exactly at once; the "
              "rest aggregate into the ~other bucket (memory is "
              "hard-capped at top_k + 1 cost vectors per replica "
              "regardless of tenant cardinality)")
register_flag("FLAGS_usage_default_tenant", "~default",
              "usage ledger: tenant every unattributed request books "
              "under when no X-PaddleTPU-Tenant header / submit("
              "tenant=) is given (kept distinct from ~other, the "
              "sketch's demoted-tenant aggregate)")
