"""Framework configuration.

Replaces the reference's gflags + builder Options pair
(reference: common/global_gflags.cpp — ~23 flags; common/options.h:24-77)
with one frozen dataclass parsed from CLI/env. Defaults mirror the
reference's flag defaults (BASELINE.md anchors).
"""

from __future__ import annotations

import argparse
import dataclasses
import os
from dataclasses import dataclass, field
from typing import List, Optional


@dataclass
class ServiceConfig:
    """Service-tier (control plane) options."""

    # Server endpoints (reference: global_gflags.cpp ports).
    host: str = "0.0.0.0"
    http_port: int = 9888
    rpc_port: int = 9889

    # Concurrency (reference defaults 32 threads / 128 concurrency).
    num_threads: int = 32
    max_concurrency: int = 128
    # Most threads the scheduler starts (as needed) for deliveries that
    # must not run on the thread that received the tokens: streams of the
    # threaded HTTP backend, whose writes can block, and upstream cancels.
    # The event backend delivers inline and starts none for its streams
    # (service/ordered_streams.py; the reference's 128 lanes,
    # scheduler.h:112).
    num_ordered_output_streams: int = 128

    # HTTP front-end backend: "event" = evserve selectors/epoll loop (SSE
    # streams hold sockets, not threads — the >1k-concurrent-streams path);
    # "threaded" = stdlib ThreadingHTTPServer (thread per connection).
    http_backend: str = "event"
    http_workers: int = 32  # event backend: route-handler pool size
    http_max_connections: int = 4096  # accept cap; extras are refused
    http_idle_timeout_s: float = 120.0  # keep-alive idle reap (<=0 disables)
    http_drain_timeout_s: float = 5.0  # stop(): grace for in-flight streams
    # Slow-client guard: per-connection SSE outbox cap. A client that falls
    # a full buffer behind its generation is dropped and the request
    # cancelled upstream, instead of buffering without bound.
    sse_max_buffered_kb: int = 512
    # Event backend request-body cap (413 past it). Must clear the largest
    # legitimate body — base64 multimodal parts run to ~100 MB of video.
    http_max_body_mb: int = 256

    # Coordination backend. "memory://" selects the in-process store;
    # "etcd://host:port" an external etcd (reference: --etcd_addr).
    etcd_addr: str = "memory://"

    # Routing policy: RR | CAR | SLO_AWARE (reference: --load_balance_policy).
    load_balance_policy: str = "RR"

    # KV block contract (reference: --block_size default 128,
    # --murmur_hash3_seed default 1024).
    block_size: int = 128
    murmur_hash3_seed: int = 1024

    # SLO targets, ms (reference: global_gflags.cpp:102-112).
    target_ttft_ms: float = 1000.0
    target_tpot_ms: float = 50.0

    # Liveness (reference: 3 s heartbeat / lease TTL; the 15 s
    # detect_disconnected_instance_interval flag is dead code there — here it
    # is real and prunes instances whose heartbeat stopped).
    heartbeat_interval_s: float = 3.0
    master_lease_ttl_s: float = 3.0
    detect_disconnected_instance_interval_s: float = 15.0
    # Floor on the instance-registration lease TTL (the TTL is otherwise
    # 3x the heartbeat interval). An engine whose heartbeat thread stalls
    # behind a long GIL-holding XLA trace/compile must not be pruned as
    # dead mid-generation; fault-injection tests that WANT fast expiry
    # lower this explicitly.
    instance_lease_min_ttl_s: float = 10.0

    # Fault hardening (docs/FAULT_TOLERANCE.md). Control-plane POSTs
    # (dispatch/cancel/encoder push) retry with jittered exponential
    # backoff up to this many attempts...
    dispatch_retry_attempts: int = 3
    # ...gated by a GLOBAL retry budget: every first attempt deposits
    # `ratio` tokens, every retry spends one (min_tokens floors the
    # bucket), so one flapping instance can't trigger a retry storm.
    retry_budget_ratio: float = 0.2
    retry_budget_min: float = 10.0
    # Circuit breaker: consecutive dispatch/cancel failures per instance
    # before it turns suspect (deprioritized) / ejected (unroutable until
    # an active /health probe passes).
    breaker_suspect_failures: int = 2
    breaker_eject_failures: int = 4
    # Mid-stream failover: total transparent replay attempts per request
    # (pre-first-token redispatch and token-replay resume share the
    # bound).
    max_redispatch: int = 2
    # Fenced master failover: instance-side TTL for in-flight manifests a
    # takeover reconciliation did NOT reclaim — past it the instance
    # reaps them (engine requests cancelled, blocks freed) so a dead
    # master's requests can never leak KV (docs/FAULT_TOLERANCE.md).
    reconcile_orphan_ttl_s: float = 10.0

    # Fleet-wide prefix KV fabric (docs/KV_CACHE.md): fetch-aware dispatch
    # hints, fetch-cost-adjusted CAR scoring, and coordinated multi-tier
    # eviction. The env var XLLM_PREFIX_FABRIC=1|0 overrides this field
    # either way (read per call, so the hatch flips on a live cluster).
    enable_prefix_fabric: bool = True

    # Goodput controller plane (cluster/goodput.py): per-request
    # colocate-vs-disaggregate placement plus continuous PD role
    # reshaping. The env var XLLM_GOODPUT_CONTROLLER=1|0 overrides this
    # field either way (read per call); when off or when its input
    # signals are stale the scheduler keeps today's static behavior.
    enable_goodput_controller: bool = True
    # Per-tenant admission control at the front door (service/admission.py):
    # token-bucket rate (req/s per tenant, 0 = unlimited), per-tenant and
    # global inflight caps, fair-share weighted queuing bounded by the
    # queue timeout (0 = shed immediately at the global cap), and
    # "tenant:weight,..." fair shares. XLLM_ADMISSION=1|0 overrides the
    # enable either way; each knob has a matching XLLM_ADMISSION_* hatch
    # read per call (docs/ARCHITECTURE.md).
    enable_admission_control: bool = True
    admission_rate: float = 0.0
    admission_burst: float = 0.0
    admission_max_inflight: int = 2048
    admission_max_global_inflight: int = 8192
    admission_queue_timeout_s: float = 2.0
    admission_weights: str = ""

    # Tokenizer / template (reference: --tokenizer_path).
    tokenizer_path: str = ""

    # Tracing (reference: --enable_request_trace).
    enable_request_trace: bool = False
    trace_dir: str = "trace"
    # Rotated trace.jsonl generations kept on disk (trace.jsonl.1..N).
    trace_keep: int = 1
    # Flight recorder (obs/flight.py, docs/OBSERVABILITY.md): always-on
    # span ring capacity per process, and the anomaly thresholds that
    # dump it — TTFT SLO in ms (0 disables the SLO trigger) and the KV
    # handoff stall bound in ms. Env hatches XLLM_TRACE_RING,
    # XLLM_TRACE_SLO_TTFT_MS and XLLM_TRACE_STALL_MS override these
    # fields either way (read at trigger time, so they flip live).
    trace_ring_capacity: int = 2048
    trace_slo_ttft_ms: float = 0.0
    trace_stall_ms: float = 2000.0

    # Decode→service direct response path (reference:
    # ENABLE_DECODE_RESPONSE_TO_SERVICE env, rpc_service/service.h:61-71).
    enable_decode_response_to_service: bool = True

    # EPD multimodal: placeholder tokens inserted per media part — must
    # match the encoder's VisionConfig.out_tokens.
    mm_tokens_per_media: int = 4
    # Real-image front door (service/image_processor.py): which HF
    # processor semantics to apply to data:image/... payloads before the
    # encode stage. "" rejects real images (raw-f32 tensor backdoor
    # only); "siglip" = resize+0.5-normalize; "qwen2vl" = smart-resize
    # pixel math pinned to the tower's square, CLIP normalize.
    mm_image_processor: str = ""
    # Square the ENCODE tower compiled for (VisionConfig.image_size);
    # required when mm_image_processor is set.
    mm_image_size: int = 0
    # Frames per temporal slice of the ENCODE tower
    # (VisionConfig.temporal_patch_size) — sizes video placeholder
    # spans: a T-frame video takes T/tps * mm_tokens_per_media tokens.
    mm_temporal_patch_size: int = 2
    # Uniform-sampling cap for real compressed videos (data:video/...):
    # longer clips sample down to this many frames before encoding.
    mm_video_max_frames: int = 16
    # Audio front door (service/audio_processor.py): the ENCODE audio
    # tower's log-mel geometry (AudioConfig.num_mel_bins / mel_frames).
    # 0 frames disables real-audio ingestion (raw-f32 backdoor only).
    mm_audio_mel_bins: int = 128
    mm_audio_mel_frames: int = 0

    # Encoder fabric (docs/EPD.md): media-hash-keyed embedding index +
    # hit/queue-aware encoder routing on the master, streamed
    # encoder->prefill handoff, and cross-request encoder batching on the
    # instances. The env var XLLM_ENCODER_FABRIC=1|0 overrides this field
    # either way (read per call, so the hatch flips on a live cluster);
    # every fabric failure degrades to the synchronous EPD path.
    enable_encoder_fabric: bool = True

    @classmethod
    def from_args(cls, argv: Optional[List[str]] = None) -> "ServiceConfig":
        parser = argparse.ArgumentParser("xllm-service-tpu master")
        for f in dataclasses.fields(cls):
            flag = "--" + f.name.replace("_", "-")
            if f.type == "bool" or isinstance(f.default, bool):
                parser.add_argument(
                    flag, type=lambda s: s.lower() in ("1", "true", "yes"),
                    default=f.default,
                )
            else:
                parser.add_argument(flag, type=type(f.default), default=f.default)
        ns = parser.parse_args(argv)
        return cls(**vars(ns))


@dataclass
class EngineConfig:
    """Engine-tier (TPU runtime) options for one instance."""

    model: str = "llama3-tiny"  # key into models/configs.py registry
    checkpoint_path: str = ""  # empty = random-init (tests/bench)
    dtype: str = "bfloat16"

    # Paged KV cache.
    block_size: int = 128  # tokens per KV block — must match service tier
    murmur_hash3_seed: int = 1024  # block-hash seed — must match service tier
    num_blocks: int = 0  # 0 = size from hbm_utilization
    hbm_utilization: float = 0.9  # fraction of HBM for params + KV pool
    # "auto" stores KV in model dtype; "int8" quantizes per (token, kv-head)
    # row — halves decode's HBM traffic and doubles pool capacity. The
    # block-hash contract is unaffected (hashes cover token ids, not bytes);
    # migration/host-tier payloads stay in model dtype (requantized on
    # import).
    kv_cache_dtype: str = "auto"
    # "auto" keeps matmul weights in model dtype; "int8" quantizes them
    # per output channel (ops/quant.py) — halves decode's weight HBM
    # traffic and per-device param residency (the 70B-on-v5e lever the
    # dress rehearsal budgets flag); "int4" packs two weights per byte
    # with group-wise scales (group 128 along the contracting axis) —
    # quarter-size weights, the DeepSeek-V3-scale-on-a-pod lever. All
    # model families.
    weight_dtype: str = "auto"

    # Continuous batching.
    max_running_requests: int = 64
    max_prefill_tokens: int = 8192  # per-step prefill token budget
    max_seq_len: int = 8192
    prefill_buckets: List[int] = field(
        default_factory=lambda: [128, 256, 512, 1024, 2048, 4096, 8192]
    )

    # Parallelism over the instance's mesh.
    dp_size: int = 1
    tp_size: int = 1
    ep_size: int = 1  # MoE expert parallelism (experts over an ep axis)
    sp_size: int = 1  # sequence/context parallelism (ring-attention prefill)
    # Prompts with at least this many uncached tokens prefill via the
    # sequence-parallel ring path (0 = never). Requires sp_size > 1.
    sp_prefill_threshold: int = 0

    # Sampling defaults.
    max_new_tokens_default: int = 512

    # Pipeline depth of the engine's one step loop (docs/ENGINE_PIPELINE.md).
    # False (default) = depth 1: step N+1 is dispatched while step N's
    # sampled tokens are still in flight on the device (they feed step
    # N+1's inputs device-side; the host drains results one step behind
    # and discards the single late token a stopped sequence
    # over-produces). True = depth 0: the same loop drains each step
    # before it returns, so every slot is host-fed, nothing is discarded
    # late and prefill runs split; differential suites use it as the
    # reference, and `--sync-engine` sets it. The engine reads the field
    # EVERY step, so a flip takes effect on a running engine at the next
    # iteration (what the pipeline held is flushed at the transition).
    sync_engine: bool = False

    # Mixed stepping. True (default) = the engine step builder emits ONE
    # batch per iteration — all active decode slots PLUS the due
    # chunked-prefill rows — served by a single compiled mixed step
    # (models.<family>.mixed_step via executor.mixed_start), so prefill
    # and decode stop competing for alternating engine steps
    # (docs/KERNELS.md); the attention inside that step is the decode and
    # the prefill launch side by side. False = the split-step escape
    # hatch (prefill batch then decode step, the pre-ISSUE-9 hot loop).
    # Depth-0 iterations (sync_engine) always run split
    # (executor.fuses_prefill decides). Guided requests ride the mixed
    # batch (their final chunk samples under an in-graph mask row), and
    # speculative engines fuse verify rows with the due prefill chunks
    # (mixed_verify_step) where the family has one (MLA families have
    # none: their speculative engines run split).
    enable_mixed_step: bool = True

    # Speculative decoding (prompt-lookup / n-gram drafting; 0 disables).
    # Each decode step drafts this many tokens per sequence by matching the
    # newest suffix n-gram against the sequence's own history, verifies all
    # of them in ONE forward pass (static [R, k+1] shapes — no recompiles),
    # and emits 1..k+1 tokens. EXACT: point-mass drafts + the sequential
    # per-step key schedule make the emitted stream bit-identical to
    # non-speculative decoding under the same seeds (ops/sampling.py
    # speculative_sample). Decode is HBM-bound, so verifying k+1 positions
    # reuses the same weight/KV traffic one token would — accepted drafts
    # are nearly free throughput. At pipeline depth 1 verify step N+1's
    # inputs (last accepted token, position, step count) are gathered ON
    # DEVICE from step N's output, and host-proposed drafts may lag one
    # step without changing a byte: point-mass acceptance makes the
    # emitted stream draft-independent (docs/ENGINE_PIPELINE.md).
    speculative_tokens: int = 0
    speculative_ngram_max: int = 3  # longest suffix n-gram to match
    # Legacy scan bound for prompt-lookup drafting. The proposer keeps a
    # per-sequence rolling suffix index (O(ngram_max) per step), so this
    # only caps the one-off index build of a long RESUMED history; the
    # index itself covers the full history.
    speculative_lookback: int = 4096

    # Persistent XLA compilation cache dir ("" disables). First boot of a
    # shape-bucketed engine compiles tens of programs at 20-40 s each on
    # TPU; with the cache, every later boot (restart, PD role flip to an
    # already-seen traffic shape, elastic scale-out on shared storage)
    # loads them in milliseconds — SURVEY.md §7 hard part 4.
    compilation_cache_dir: str = ""

    # Host offload (DRAM tier) blocks; 0 disables.
    num_host_blocks: int = 0
    # SSD tier: blocks spilled from the host pool to local disk; 0 disables.
    num_ssd_blocks: int = 0
    ssd_cache_dir: str = ""  # empty = <tempdir>/xllm-ssd-cache-<pid>

    # PD KV handoff to a decode peer in the SAME process goes through a
    # direct call (no serialization — single-host ICI-path analog) when
    # enabled; disable to force the HTTP data plane.
    enable_local_kv_transfer: bool = True

    # Pipelined PD handoff (docs/PD_DISAGGREGATION.md): stream each
    # prefill chunk's completed KV blocks to the decode peer WHILE the
    # next chunk is still prefilling, so only the tail rides the
    # post-prefill commit and the handoff stall shrinks to the tail +
    # control round-trip. Single-chunk prompts always take the monolithic
    # path; any session failure falls back to it too. The env var
    # XLLM_PD_STREAMING=1|0 overrides this field either way (the escape
    # hatch is read per request, so it can flip on a live instance).
    enable_pd_streaming: bool = True

    # Fleet-wide prefix KV fabric, instance side (docs/KV_CACHE.md): serve
    # peer /kv/fetch requests, act on dispatch fetch hints, and offer
    # last-replica evictions to the master's coordinator. The env var
    # XLLM_PREFIX_FABRIC=1|0 overrides either way, per request.
    enable_prefix_fabric: bool = True

    # Encoder fabric, instance side (docs/EPD.md): ENCODE instances grow a
    # cross-request micro-batcher + media-hash-keyed embedding LRU, and
    # the encoder->prefill handoff streams per-item sessions instead of
    # one monolithic /mm/import. XLLM_ENCODER_FABRIC=1|0 overrides either
    # way, per request; any failure degrades to the synchronous path.
    enable_encoder_fabric: bool = True
    # Micro-batcher admission window: an arriving media item waits at most
    # this long for same-kind items from OTHER requests before the tower
    # dispatch fires (deadline-bounded coalescing).
    encoder_batch_window_ms: float = 5.0
    # Micro-batcher size bound (power of two — the towers pad batches to
    # pow2, so a pow2 cut wastes no padding).
    encoder_batch_max: int = 8
    # Encoder-local embedding LRU capacity, in media items (0 disables
    # caching; the master's fleet index follows via heartbeat deltas).
    encoder_cache_entries: int = 256
    # Prefill side: how long an admitted media request may wait for its
    # streamed embeddings before it is rejected (generous — the encoder's
    # first request pays its XLA compile inside this window).
    mm_stream_deadline_s: float = 180.0

    # Cross-PROCESS device-to-device KV data plane
    # (jax.experimental.transfer). When enabled, PD handoffs to a peer in
    # another process are OFFERED on this process's transfer server and
    # pulled by the peer straight into its device memory — the payload
    # never stages through host RAM on either side (the reference's
    # engine-to-engine RDMA pull, types.h:174-177). Disabled: payload
    # bytes ride the /kv/import POST body.
    enable_kv_transfer_server: bool = False
    kv_transfer_listen: str = "127.0.0.1:0"

    # Multi-host process group (jax.distributed). Non-empty
    # coordinator_address bootstraps the group before the mesh is built;
    # jax.devices() then spans ALL hosts and dp/tp/ep/sp shardings ride
    # ICI within a slice and DCN across hosts. num_processes/process_id
    # may stay 0/-1 on real TPU pods (auto-discovered from metadata).
    coordinator_address: str = ""
    num_processes: int = 0
    process_id: int = -1

    # Compile the serving step functions (per-bucket prefill + decode)
    # BEFORE the instance registers, so the first real request never pays
    # a compile in its TTFT.
    warmup_on_start: bool = False

    # Instance identity/role.
    instance_name: str = ""
    instance_type: str = "MIX"  # DEFAULT | PREFILL | DECODE | MIX | ENCODE

    # Instance HTTP front door backend ("threaded" | "event"); the service
    # tier's equivalent knob is ServiceConfig.http_backend. Threaded stays
    # the default here: direct-mode streaming handlers block their worker,
    # so the event loop's pool would cap direct-mode concurrency.
    http_backend: str = "threaded"
