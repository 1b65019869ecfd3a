"""Continuous-batching inference engine.

Engine-tier core (the reference's analog lives in the absent xLLM submodule;
this implements the runtime its service layer assumes — SURVEY.md §2.3):
admission with prefix-cache reuse, one fixed-shape decode step per iteration
over R slots, incremental block allocation with recompute-preemption, block
commits under chained hashes, and heartbeat-ready load/latency metrics +
KV cache events (proto contract: xllm_rpc_service.proto:44-58).

Pure host-side orchestration: all device work goes through ModelExecutor's
two jitted step functions, so nothing here ever triggers a recompile.
"""

from __future__ import annotations

import collections
import json
import logging
import math
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Deque, Dict, List, Optional, Set, Tuple

import numpy as np

from xllm_service_tpu.common.concurrency import (
    claim_thread,
    release_thread,
    thread_owned,
)
from xllm_service_tpu.common.config import EngineConfig
from xllm_service_tpu.common.hashing import prefix_block_hashes
from xllm_service_tpu.common.types import (
    FinishReason,
    KvCacheEvent,
    LatencyMetrics,
    LoadMetrics,
    LogProb,
    LogProbData,
    RequestOutput,
    SequenceOutput,
    Status,
    StatusCode,
    Usage,
)
from xllm_service_tpu.models.configs import PARALLEL_KIND
from xllm_service_tpu.obs import (
    BATCH_BUCKETS,
    ENGINE_PHASES,
    LATENCY_BUCKETS_MS,
    EnginePhases,
    MetricsRegistry,
)
from xllm_service_tpu.obs import startup as obs_startup
from xllm_service_tpu.ops.sampling import SamplingParams
from xllm_service_tpu.runtime.block_manager import (
    BlockManager,
    OutOfBlocksError,
    HybridBlockManager,
    StateSlotManager,
    WindowBlockManager,
)
from xllm_service_tpu.runtime import compile_cache as compile_cache_mod
from xllm_service_tpu.runtime.executor import (
    ModelExecutor,
    SamplingBatch,
    fuses_prefill,
)


@dataclass
class EngineRequest:
    request_id: str
    prompt_token_ids: List[int]
    sampling: SamplingParams
    # Called from the engine thread once per generated token (and once on
    # finish); return False to cancel (reference OutputCallback contract,
    # common/xllm/output.h:131).
    callback: Callable[[RequestOutput], bool]
    arrival_time: float = field(default_factory=time.monotonic)
    # Stamped by InferenceEngine.add_request: the start of the engine's
    # queue wait (xllm_engine_queue_wait_ms), zeroed again when the first
    # prefill chunk dispatches. arrival_time above is when the request
    # object was built and orders preemption; it is not this.
    queued_at: float = 0.0
    # PD disaggregation (prefill side): emit the first token, then hand the
    # sequence off instead of decoding (reference flow: prefill instance
    # returns the first chunk, decode instance continues —
    # rpc_service/service.h:61-71). `handoff` receives a KVHandoff.
    prefill_only: bool = False
    handoff: Optional[Callable[["KVHandoff"], None]] = None
    # Pipelined PD handoff (docs/PD_DISAGGREGATION.md): when set on a
    # prefill_only request, the chunked-prefill loop calls
    # `kv_stream.send_chunk(KVStreamChunk)` on the engine thread after each
    # PARTIAL chunk lands, exporting the newly completed full blocks while
    # the next chunk is still prefilling. The hook returns True when the
    # chunk was accepted for delivery (the blocks then ride the stream and
    # the final handoff carries only the tail); False — or a later
    # `kv_stream.aborted` — makes the final handoff monolithic again
    # (kv_start_block=0, full export). Single-chunk prompts never call it.
    kv_stream: Optional[object] = None
    # EPD multimodal: encoder-produced media embeddings [m, E] injected at
    # these absolute prompt positions (placeholder tokens). Requests with
    # media bypass the prefix cache — placeholder ids alone cannot key
    # content-addressed blocks across different images.
    mm_embeds: Optional[object] = None
    mm_positions: Optional[object] = None
    # Streamed encoder handoff (docs/EPD.md): embeddings are still
    # arriving per-item over the /mm/chunk session while this request is
    # admitted. The admission loop gates each prefill chunk on
    # `mm_stream.ready_upto(chunk_end)` — text chunks before the first
    # uncovered placeholder prefill WHILE the encoder streams — and
    # materializes mm_embeds/mm_positions from `assembled()` once every
    # item landed. Expiry (mm_stream_deadline_s) rejects the request;
    # abort alone does not (the monolithic fallback push completes it).
    mm_stream: Optional[object] = None
    # Per-media merged-token grids [(t, gh, gw), ...] in document order
    # (t > 1 = video): _mrope_positions lays the (t, h, w) streams from
    # these instead of inferring a square still-image grid from the span
    # length. Absent/short lists fall back to the inference.
    mm_grids: Optional[object] = None
    # Guided decoding: "json" constrains the output to a JSON object via
    # the engine's mask table (set_guided_context must have been called);
    # "json_schema" additionally constrains it to `schema` (a JSON-Schema
    # dict in the supported strict subset — guided/schema_fsm).
    guided: Optional[str] = None
    schema: Optional[dict] = None
    # Multi-LoRA adapter row in the executor's stacks (0 = base model).
    adapter_idx: int = 0
    # Mid-stream failover resume: the last `resume_from` entries of
    # prompt_token_ids are REPLAYED generation output from a dead
    # instance, not client prompt. The real engine needs no special
    # handling (re-prefill + continue IS resume; prefix caching makes the
    # replay cheap); deterministic stand-ins (FakeEngine) use it to keep
    # the continuation byte-identical to the unfaulted stream.
    resume_from: int = 0
    # Hybrid online/offline (north-star config 5; reference vestige
    # request.h:38, unconsumed there): offline work admits only behind
    # online work and its RUNNING decodes are preempted (recompute-style)
    # when online requests are waiting for slots or blocks.
    offline: bool = False

    @property
    def has_media(self) -> bool:
        return (
            self.mm_embeds is not None or self.mm_stream is not None
        ) and len(self.mm_positions or ()) > 0


@dataclass
class KVHandoff:
    """Everything a decode peer needs to continue a prefilled sequence.

    Only FULL committed blocks migrate; the sub-block tail (< block_size
    tokens plus the first generated token) is recomputed by the importer's
    prefill path, which keeps the chained-hash prefix-cache semantics exact
    on both sides. The TPU analog of the reference's RDMA KV pull whose
    handles the service relays (types.h:174-177): in-process peers receive
    `kv` as a device array (ICI path: jax.device_put to the peer mesh);
    cross-host peers receive it serialized over the data plane (DCN path).
    """

    request_id: str
    # prompt + the first generated token
    token_ids: List[int]
    first_token: int
    first_logprob: float
    num_full_blocks: int
    # chained hashes of the migrated full blocks, in order
    block_hashes: List[bytes]
    # [2, L, num_full_blocks - kv_start_block, Hkv, BS, D] (k, v stacked);
    # None when no full blocks remain to carry (short prompt -> pure
    # recompute on the decode side, or every block already rode the
    # streaming session)
    kv: Optional[object]
    usage_prompt_tokens: int = 0
    # Pipelined handoff: blocks [0, kv_start_block) were already delivered
    # through the per-chunk streaming session (they sit committed in the
    # importer's prefix cache); `kv` covers [kv_start_block,
    # num_full_blocks). 0 = monolithic payload, exactly the old contract.
    kv_start_block: int = 0


@dataclass
class KVStreamChunk:
    """One pipelined-handoff chunk: the full blocks completed by a partial
    prefill chunk, exported while later chunks are still prefilling.

    `block_hashes` are the chained hashes of blocks [start_block,
    start_block + n); `kv` is the device export [2, L, n, Hkv, BS, D]. The
    importer lands them straight into its prefix cache (content-addressed
    commit), so delivery order across chunks does not matter and a lost
    chunk only costs recompute of its span — never correctness."""

    request_id: str
    start_block: int
    block_hashes: List[bytes]
    kv: object
    prompt_tokens: int
    # Total full blocks the whole prompt will migrate (session sizing /
    # receive-side reservation hint).
    total_blocks_hint: int = 0


class _Seq:
    __slots__ = (
        "req", "slot", "tokens", "block_ids", "num_cached", "generated",
        "last_committed_block", "prefill_done_time", "last_token_time",
        "prefilled", "chunk_len", "prefill_start_time", "head_hash",
        "json_state", "json_upto", "schema_spec",
        "rope_pos3", "rope_delta", "admit_gen", "streamed_blocks",
        "stream_hashes", "admit_hashes", "pf_dispatched",
        "spec_ngrams", "spec_idx_upto", "window_lo",
    )

    def __init__(self, req: EngineRequest, slot: int):
        self.req = req
        self.slot = slot
        self.tokens: List[int] = list(req.prompt_token_ids)
        self.block_ids: List[int] = []
        # A window family: the first logical block that may still hold a
        # block of the window pool (block_manager.WindowBlockManager.slide).
        self.window_lo = 0
        self.num_cached = 0
        self.generated: List[Tuple[int, float]] = []  # (token, logprob)
        self.last_committed_block = -1  # index into block_ids
        self.prefill_done_time = 0.0
        self.last_token_time = 0.0
        # Chunked-prefill state: `prefilled` = prompt tokens whose KV is
        # already in this seq's cache blocks (>= num_cached once the first
        # partial chunk lands); `chunk_len` = this step's budgeted chunk.
        # A mid-prefill seq waits in the queue HOLDING its slot and blocks
        # (continued FIRST each step); decode steps run between chunks.
        self.prefilled = 0
        self.chunk_len = 0
        self.prefill_start_time = 0.0  # first chunk's t0 (true TTFT base)
        self.head_hash: Optional[bytes] = None  # block-0 chained hash
        # Guided decoding: exact JSON automaton state consumed up to
        # generated[json_upto]; lazily advanced by _guided_row (survives
        # preemption with the _Seq; rebuilt on PD import since the state
        # walks `generated`). None after an automaton reject = permissive
        # from then on (never expected under the mask; belt+braces).
        self.json_state = "INIT"
        self.json_upto = 0
        self.schema_spec = None  # compiled SchemaSpec, cached at first use
        # Qwen2-VL M-RoPE: [3, prompt_len] position streams + the (<= 0)
        # lag of generation rope positions behind token counts; None/0
        # for everything but media prompts on an mrope model.
        self.rope_pos3 = None
        self.rope_delta = 0
        # Pipelined PD handoff: full blocks already exported through the
        # request's kv_stream hook (the final handoff carries only
        # [streamed_blocks, num_full_blocks)); `stream_hashes` caches the
        # chained block hashes, extended incrementally per chunk.
        self.streamed_blocks = 0
        self.stream_hashes: List[bytes] = []
        # Admission-time chained hashes of the prompt's full blocks: the
        # mid-prefill re-match (_extend_midchunk_match) walks them at every
        # chunk boundary so blocks that land DURING chunked prefill — a
        # fabric peer fetch, a streamed PD chunk, another sequence's
        # commit — are adopted instead of recomputed. Empty for
        # media/LoRA requests (they bypass the cache).
        self.admit_hashes: List[bytes] = []
        # Bumped by _slot_admit: distinguishes a re-admission of the SAME
        # sequence object from the occupancy an in-flight step sampled for
        # (preempt + same-pass resume into the same slot must not let the
        # stale in-flight token through the drain's identity check).
        self.admit_gen = 0
        # Mixed stepping: prompt tokens DISPATCHED through
        # prefill chunks, >= `prefilled` while a chunk is in flight — the
        # step builder cuts the next chunk from here so back-to-back
        # chunks pipeline instead of waiting out each drain.
        self.pf_dispatched = 0
        # Prompt-lookup drafting index (speculative decode): suffix
        # n-gram -> follow position over this sequence's own history,
        # extended incrementally per emitted token so proposing k drafts
        # is O(ngram_max^2) per step instead of a full history rescan
        # (_propose_drafts). `spec_idx_upto` = history length whose
        # gram-ends are indexed (always one short of len(tokens): the
        # newest gram has no follow token yet and must never self-match).
        self.spec_ngrams: Dict[tuple, int] = {}
        self.spec_idx_upto = 0


class _InFlight:
    """One dispatched-but-undrained decode step (overlapped pipeline).

    `tokens`/`logprobs` are DEVICE arrays still being computed; `slots`
    snapshots slot -> (_Seq, admit_gen) at dispatch time so the drain can
    tell whether a slot still belongs to the exact occupancy it sampled for
    (a seq finished, cancelled, preempted — or preempted and re-admitted —
    between dispatch and drain gets its late token discarded: the
    one-step-late stop semantics, docs/ENGINE_PIPELINE.md)."""

    __slots__ = (
        "tokens", "logprobs", "slots", "t0", "nactive", "total_ctx", "pf",
        "n_emit", "pf_tok", "pf_lp", "feed", "moe",
    )

    def __init__(
        self, tokens, logprobs, slots, t0, nactive, total_ctx, pf=(),
        n_emit=None, pf_tok=None, pf_lp=None, feed=None, moe=(),
    ):
        self.tokens = tokens
        # An expert model's routing counts of this dispatch (device
        # arrays; executor.take_moe_stats), read when the tokens are.
        self.moe = moe
        self.logprobs = logprobs
        # What the next dispatch takes as its device-side feedback: the
        # decode slots' tokens, [R]. A mixed step hands them out on their
        # own (its `tokens` are [R + P]); everywhere else they ARE `tokens`.
        self.feed = tokens if feed is None else feed
        self.slots = slots
        self.t0 = t0
        self.nactive = nactive
        self.total_ctx = total_ctx
        # Mixed step: [(seq, admit_gen, row_idx, chunk_start,
        # chunk_end, queued_ms — the engine queue wait on a request's
        # first chunk, else None)] prefill rows riding this dispatch —
        # their sampled tokens sit at output index R + row_idx
        # (docs/KERNELS.md), or in pf_tok/pf_lp when this is a
        # speculative verify step.
        self.pf = pf
        # Pipelined speculative verify: tokens/logprobs are [R, S] and
        # each slot consumes its first n_emit[slot] entries at drain
        # (None = plain decode step). pf_tok/pf_lp carry the fused
        # prefill rows' samples ([P]) for verify steps.
        self.n_emit = n_emit
        self.pf_tok = pf_tok
        self.pf_lp = pf_lp


# get_latency_metrics looks back at most this far (its default is 30 s).
LATENCY_WINDOW_S = 60.0
# Samples kept of each TimePredictor seed curve (engine.profiling_data).
PROFILE_SAMPLES = 512

# The waiting queue holds fresh EngineRequests and preempted _Seqs (which
# resume with their full token history + generation accounting intact).
_QueueItem = "EngineRequest | _Seq"


def _on_roomy_stack(fn):
    """Call `fn()` from a frame that holds room for every call below it.

    CPython keeps a thread's frames in 16 KiB chunks and unmaps a chunk
    the moment the frame that opened it returns. JAX's tracing and
    lowering recurse across such a boundary thousands of times a
    program, an mmap/munmap pair each time: of the 30 s the engine
    thread spent tracing and lowering chat-steady's 16 step programs,
    20 s were that, and WHICH calls straddle a boundary follows the
    locals of every caller, so set-up moved by seconds with edits that
    touched no program (PERF.md section 6, PR 35). A frame that declares
    a 64 Ki-word operand stack makes the interpreter open one 1 MiB
    chunk for it instead; the loop and all it calls live in its rest."""
    return fn()


_on_roomy_stack.__code__ = _on_roomy_stack.__code__.replace(
    co_stacksize=1 << 16
)


class InferenceEngine:
    @obs_startup.startup_phase("engine")
    def __init__(
        self,
        engine_cfg: EngineConfig,
        executor: Optional[ModelExecutor] = None,
        eos_token_ids: Tuple[int, ...] = (),
    ):
        self.cfg = engine_cfg
        self.executor = executor or ModelExecutor(engine_cfg)
        self.eos_token_ids = set(eos_token_ids)
        self.block_size = self.executor.block_size
        self.R = self.executor.R
        self.max_blocks = self.executor.max_blocks_per_seq
        from xllm_service_tpu.runtime.native_blocks import create_block_manager

        # A state-pool family (power retention, models/brumby.py): the
        # executor's block is as long as max_seq_len, so the block
        # arithmetic below gives every sequence exactly ONE block, its
        # state slot, for its life. The block is full only at exactly
        # max_seq_len tokens, on the way to LENGTH, and nothing is ever
        # hashed, committed or matched (_commit_full_blocks returns early;
        # block_manager.StateSlotManager's content-addressed half is
        # inert). Preemption frees the slot; the resumed sequence
        # recomputes its tokens from position 0.
        # A family with a state pool BESIDE a paged cache
        # (models/granite.py) keeps ordinary blocks for its K/V and the
        # state in the sequence's row (`seq.slot`, which every PrefillItem
        # carries): the same rules, with blocks that grow.
        self.state_family = bool(
            getattr(self.executor, "has_state_pool", False)
        )
        self.state_recomputes = 0
        # A family with window layers (models/granite.py) has a second
        # paged pool: a sequence's table into it rides behind the full
        # pool's columns of every table the engine builds (`_slide_window`)
        # and its blocks are freed behind the sequence.
        self.window_family = bool(getattr(self.executor, "window_tables", False))
        if self.window_family:
            self.window = self.executor.cfg.sliding_window
            self.block_mgr = WindowBlockManager(
                self.executor.num_blocks, self.executor.window_blocks,
                self.block_size, seed=engine_cfg.murmur_hash3_seed,
            )
        elif self.state_family and self.executor.has_paged_cache:
            self.block_mgr = HybridBlockManager(
                self.executor.num_blocks, self.block_size,
                seed=engine_cfg.murmur_hash3_seed,
            )
        elif self.state_family:
            self.block_mgr = StateSlotManager(
                self.R, self.block_size, seed=engine_cfg.murmur_hash3_seed
            )
        else:
            self.block_mgr = create_block_manager(
                self.executor.num_blocks, self.block_size,
                seed=engine_cfg.murmur_hash3_seed,
            )
        # Host (DRAM) cache tier: committed blocks evicted from HBM are
        # copied to host memory and re-imported on a later prefix match
        # (num_host_blocks=0 disables — reference tier contract proto:47).
        # The SSD tier catches DRAM's own evictions on local disk.
        self.host_pool = None
        self.ssd_pool = None
        if engine_cfg.num_host_blocks > 0:
            from xllm_service_tpu.runtime.host_cache import HostKVPool, SsdKVPool

            self.host_pool = HostKVPool(engine_cfg.num_host_blocks)
            self.block_mgr.on_evict = self._offload_to_host
            if engine_cfg.num_ssd_blocks > 0:
                import os
                import tempfile

                directory = engine_cfg.ssd_cache_dir or os.path.join(
                    tempfile.gettempdir(), f"xllm-ssd-cache-{os.getpid()}"
                )
                self.ssd_pool = SsdKVPool(
                    directory, engine_cfg.num_ssd_blocks
                )

        self._waiting: Deque[EngineRequest] = collections.deque()  # guarded by: self._lock
        # KV imports from prefill peers, landed on the engine thread
        # (BlockManager is engine-thread-only).
        self._pending_imports: Deque[Tuple[EngineRequest, KVHandoff]] = (
            collections.deque()
        )
        # Streamed-chunk blocks from a pipelined PD handoff, landed on the
        # engine thread ahead of the session's commit.
        self._pending_kv_chunks: Deque[Tuple[List[bytes], object]] = (
            collections.deque()
        )
        # Prefix-fabric export requests (peer /kv/fetch): served on the
        # engine thread — the block manager and host/SSD pools are
        # engine-thread-only, and an off-thread export could read a block
        # mid-eviction. Each entry: {"hashes", "event", "result"}.
        self._pending_exports: Deque[dict] = collections.deque()  # guarded by: self._lock
        # Prefix-fabric coordinated eviction hook: called on the engine
        # thread as on_cold_evict(block_hash, host_kv) when a committed
        # block is about to leave the LAST local tier (host-pool eviction
        # with no SSD tier below it). Must never block — the instance
        # layer enqueues the offer and returns.
        self.on_cold_evict = None
        # Distributed-tracing hook: span_hook(request_id, stage, **fields)
        # set by the instance layer ONLY when tracing is enabled — None
        # keeps the token path free of any per-step tracing work.
        self.span_hook = None
        # Step listeners (add_step_listener): run on the stepping thread
        # where a step's booking ends and once more where step() ends, if
        # a callback has run since the last time (`_unhanded`). An owner
        # whose callbacks only collect hands a step over there, once.
        self._step_listeners: List = []
        self._step_thread: Optional[int] = None  # ident, while in step()
        self._unhanded = False
        self._running: Dict[int, _Seq] = {}  # slot -> seq
        self._free_slots = list(range(self.R - 1, -1, -1))
        self._lock = threading.Lock()
        self._work = threading.Event()
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        self._cancelled: set = set()  # guarded by: self._lock

        # Stepping is ONE loop (step()); what differs between flavours is
        # data read every iteration, not a code path chosen here: the
        # pipeline depth (`_force_sync`: cfg.sync_engine), whether due
        # prefill chunks ride the dispatch (`mixed_step_enabled`:
        # executor.fuses_prefill) and cfg.speculative_tokens.
        # Sequences mid-chunked-prefill under mixed stepping: they hold
        # slot + blocks (like split mode's waiting-held mid-chunk seqs)
        # but live HERE, keyed by request id, so the step builder can cut
        # chunk c+1 while chunk c is still in flight.
        self._pf_active: Dict[str, _Seq] = {}

        # Persistent decode-batch state: per-slot arrays mutated ONLY on
        # admit/finish/cancel/preempt (plus vectorized per-step position and
        # step-count advances) — the per-step O(R) SamplingBatch rebuild is
        # gone from the hot loop. `_ps_gen` bumps on every slot mutation and
        # keys the packed logit-bias cache.
        R = self.R
        # (a window family: the window pool's columns behind the full pool's)
        self._block_tables = np.zeros(
            (R, self.max_blocks * (2 if self.window_family else 1)), np.int32
        )
        self._ps_gen = 0
        self._ps_temps = np.zeros((R,), np.float32)
        self._ps_top_k = np.zeros((R,), np.int32)
        self._ps_top_p = np.ones((R,), np.float32)
        self._ps_seeds = np.zeros((R,), np.uint32)
        self._ps_steps = np.zeros((R,), np.int32)
        self._ps_presence = np.zeros((R,), np.float32)
        self._ps_frequency = np.zeros((R,), np.float32)
        self._ps_min_p = np.zeros((R,), np.float32)
        self._ps_adapter = np.zeros((R,), np.int32)
        self._ps_rope_delta = np.zeros((R,), np.int32)
        self._n_min_p = 0
        self._n_adapter = 0
        self._n_rope = 0
        self._n_bias = 0
        self._bias_rows: List[tuple] = [()] * R
        self._bias_cache: Tuple[Optional[np.ndarray], Optional[np.ndarray]] = (
            None, None,
        )
        self._bias_cache_gen = -1
        self._guided_slots: set = set()
        # Dispatch-side virtual state: positions/steps run one token AHEAD
        # of seq.tokens while a step is in flight (_ps_pending = dispatched
        # but not yet drained, 0 or 1 under one-step lookahead). `_fresh`
        # marks slots whose next input token must come from the host
        # (admission/resume/sync drain) instead of the in-flight device
        # sample.
        self._ps_active = np.zeros((R,), bool)
        self._ps_last_tok = np.zeros((R,), np.int32)
        self._ps_positions = np.zeros((R,), np.int32)
        self._ps_pending = np.zeros((R,), np.int32)
        self._ps_gen_count = np.zeros((R,), np.int32)
        self._ps_tok_count = np.zeros((R,), np.int32)
        self._ps_max_new = np.zeros((R,), np.int32)
        self._fresh = np.zeros((R,), bool)
        self._inflight: Optional[_InFlight] = None
        # Overlap accounting (exported via metrics + bench --engine-mode).
        self.decode_dispatches = 0
        self.mixed_steps = 0  # mixed dispatches actually carrying pf rows
        self.overlap_steps = 0
        # Collective-overlap accounting (ISSUE 18): dispatches whose
        # traced programs carry the ring collective-matmul schedule.
        # Resolved ONCE here — the hatch bakes into the jitted steps at
        # first trace, so a mid-run env flip doesn't change the programs
        # and must not change the count.
        self._overlap_collectives = (
            1 if getattr(self.executor, "overlap_collectives_active", False)
            else 0
        )
        self.collective_overlap_steps = 0
        self.late_stop_discards = 0
        self.loop_errors = 0
        self.kv_chunk_land_errors = 0
        self.host_gap_ms_sum = 0.0
        self.host_gap_steps = 0
        self._t_host_free: Optional[float] = None
        # Latency windows (ms) for LatencyMetrics: (t, ms) per finished
        # prefill and (t, worst tbt ms) per drained STEP. Only the engine
        # thread mutates them (append + trim past LATENCY_WINDOW_S);
        # get_latency_metrics reads a GIL-atomic copy from other threads.
        self._ttft_window: Deque[Tuple[float, float]] = collections.deque()
        self._tbt_window: Deque[Tuple[float, float]] = collections.deque()
        # TimePredictor seed curves, sent once at instance registration
        # (profiling_data): the first PROFILE_SAMPLES of each, then no more.
        self._profile_ttft: List[Tuple[int, float]] = []
        self._profile_tpot: List[Tuple[int, int, float]] = []
        # Work done by prefill, as counts (exported by pull functions).
        self.prefill_chunks = 0
        self.prefill_tokens = 0  # tokens computed; cached prefix excluded
        # Guided decoding context (set_guided_context): device mask table
        # lives on the executor; the engine keeps token bytes + row
        # liveness for exact host tracking.
        self._guided_tokens: Optional[List[bytes]] = None
        self._guided_row_any: Optional[np.ndarray] = None
        # json_schema mode: compiled specs by canonical schema key, the
        # (schema, exact-state) -> dynamic-row memo, the next free row in
        # the executor table's dynamic region, and the lazily built
        # first-byte token index the bitmap builder prefilters with.
        self._schema_specs: Dict[str, object] = {}
        self._schema_row_cache: Dict[tuple, int] = {}
        self._schema_row_next = 0
        self._schema_fbi = None
        self._schema_flush_pending = False
        # (schema, exact-state) -> [V] bool bitmap, shared between the
        # engine step loop and prewarm_schema (HTTP admission threads):
        # the vocab-wide Python byte walk is the expensive part of a
        # first state visit, and precomputing it at admission keeps the
        # step loop from stalling every running decode (advisor finding,
        # round 4). Plain dict ops are GIL-atomic; values are immutable.
        self._schema_bitmap_cache: Dict[tuple, np.ndarray] = {}
        self._prewarmed_schema_keys: set = set()
        self._guided_eos: Optional[List[int]] = None
        # Speculative-decoding accounting: verify steps run, slot-steps
        # (active sequences summed over steps), and tokens emitted — the
        # mean tokens/slot-step is the realized speedup over plain decode.
        self.spec_steps = 0
        self.spec_slot_steps = 0
        self.spec_tokens_emitted = 0
        # Composed-path accounting (ISSUE 13): verify steps dispatched
        # at pipeline depth 1 vs drained at depth 0, pipelined
        # dispatches that applied a guided mask row in-graph, and the
        # per-slot guided fallback — host-paced skips (a guided slot held
        # out of one dispatch so its NEXT mask row derives from the exact
        # host automaton state; the engine itself never flushes).
        self.spec_pipeline_steps = 0
        self.spec_sync_steps = 0
        self.guided_ingraph_steps = 0
        self.guided_paced_skips = 0
        # Prefix-cache effectiveness over fresh admissions (bench/metrics).
        self.prefix_cached_tokens = 0
        self.prefix_prompt_tokens = 0
        # Blocks adopted by the mid-prefill re-match (chunk-boundary cache
        # pickup of blocks that landed AFTER admission — fabric fetches,
        # streamed PD chunks, sibling commits).
        self.midprefill_adopted_blocks = 0
        # Recompute-preemption accounting (any cause: pool pressure,
        # hybrid-scheduling eviction).
        self.preemptions = 0
        self._build_metrics()
        # The executor's synchronous entry points (decode, verify,
        # prefill_batch, prefill_long) block on their results inside the
        # call: that read is the loop's `device_wait`, not `dispatch`.
        self.executor.fetch_scope = (
            lambda: self._phases.phase("device_wait")
        )

    def _build_metrics(self) -> None:
        """Engine registry (obs.metrics), rendered into the instance's
        /metrics and scraped by the master under an instance label. Hot
        paths observe histograms directly; everything already counted by
        an attribute (preemptions, prefix-cache, block manager, host
        tiers) exports via pull functions so the step loop pays nothing
        extra."""
        self.metrics = MetricsRegistry()
        self._m_ttft = self.metrics.histogram(
            "xllm_engine_ttft_ms", "Dispatch of a request's first prefill "
            "chunk to its first token (add_request to that dispatch is "
            "xllm_engine_queue_wait_ms; the two sum to the engine's TTFT)",
            buckets=LATENCY_BUCKETS_MS,
        )
        self._m_queue_wait = self.metrics.histogram(
            "xllm_engine_queue_wait_ms", "add_request to the dispatch of "
            "the request's first prefill chunk, once per request (first "
            "dispatch to first token is xllm_engine_ttft_ms)",
            buckets=LATENCY_BUCKETS_MS,
        )
        self.metrics.counter(
            "xllm_engine_prefill_chunks_total",
            "Prefill chunks dispatched (rows of mixed steps and of split "
            "prefill steps)",
        ).set_function(lambda: self.prefill_chunks)
        self.metrics.counter(
            "xllm_engine_prefill_tokens_total",
            "Prompt tokens computed by prefill (cached prefix excluded)",
        ).set_function(lambda: self.prefill_tokens)
        # Engine step timeline (docs/OBSERVABILITY.md): the engine
        # thread's seconds by exclusive phase; the same scopes are
        # xllm.engine.<phase> annotations on the profiler's clock.
        loop_seconds = self.metrics.counter(
            "xllm_engine_loop_seconds_total",
            "Engine thread time by exclusive loop phase",
            labelnames=("phase",),
        )
        phase_inc = self._phase_inc = {
            p: loop_seconds.labels(phase=p).inc for p in ENGINE_PHASES
        }
        self._phases = EnginePhases(lambda p, dt: phase_inc[p](dt))
        # Start-up timeline (docs/OBSERVABILITY.md): the process's phases
        # of a start and its trace / lower / compile seconds by program.
        obs_startup.TIMELINE.export(self.metrics)
        self._m_tbt = self.metrics.histogram(
            "xllm_engine_tbt_ms", "Time between tokens per running "
            "sequence", buckets=LATENCY_BUCKETS_MS,
        )
        self._m_batch = self.metrics.histogram(
            "xllm_engine_decode_batch_size",
            "Active sequences per decode step (batch occupancy)",
            buckets=BATCH_BUCKETS,
        )
        self._m_steps = self.metrics.counter(
            "xllm_engine_decode_steps_total", "Decode (or verify) steps "
            "executed",
        )
        # What the sampler had to do this step (ops/sampling.py: its work
        # follows the rows): the decode half's slots, the live ones of
        # them, and the live ones that draw (temperature > 0); drawn /
        # slots is the share of a whole-batch draw that is still made.
        sample_rows = self.metrics.counter(
            "xllm_engine_sample_rows_total",
            "Decode rows handed to the sampler, by kind: slots (every "
            "row of the fixed batch), live (dispatched rows), drawn "
            "(live rows with temperature > 0)",
            labelnames=("kind",),
        )
        self._sample_rows_inc = tuple(
            sample_rows.labels(kind=k).inc
            for k in ("slots", "live", "drawn")
        )
        self.metrics.counter(
            "xllm_engine_dispatch_h2d_total",
            "Host->device puts made by the executor's dispatch entry "
            "points (per decode step: 1, the per-slot pack; 2 on a mixed "
            "step, the prefill rows' pack; one more for each optional "
            "feature that rides)",
        ).set_function(lambda: getattr(self.executor, "dispatch_h2d", 0))
        # Expert models: pairs that fell to each held expert in one step
        # and one layer (the step program's own counts over its expert
        # layers, read with its tokens; the group size the grouped
        # expert product works at). No observation where no expert is.
        self._m_moe_pairs = self.metrics.histogram(
            "xllm_engine_moe_pairs_per_expert",
            "Routed pairs that fell to a held expert, per step and "
            "expert layer, one observation a held expert",
            buckets=BATCH_BUCKETS,
        )
        self.metrics.gauge(
            "xllm_engine_cache_row_bytes",
            "Bytes one token holds in the paged pool, over every layer "
            "and cache of it (0 for a state-pool family)",
        ).set_function(
            lambda: getattr(self.executor, "cache_row_bytes", 0)
        )
        # State-pool families (power retention): the pool and who holds
        # it. Registered for every engine; zero where there is no pool.
        self._m_state_in_use = self.metrics.histogram(
            "xllm_engine_state_slots_in_use",
            "State slots owned by a sequence (decoding or mid-prefill), "
            "observed every step", buckets=BATCH_BUCKETS,
        )
        self.metrics.gauge(
            "xllm_engine_state_slots", "Slots of the state pool",
        ).set_function(lambda: self.R if self.state_family else 0)
        self.metrics.gauge(
            "xllm_engine_state_pool_bytes", "Device bytes of the state pool",
        ).set_function(
            lambda: getattr(self.executor, "state_pool_bytes", 0)
            if self.state_family else 0
        )
        self.metrics.gauge(
            "xllm_engine_state_slot_bytes",
            "Device bytes of ONE state slot, over every layer that has one",
        ).set_function(
            lambda: getattr(self.executor, "state_slot_bytes", 0)
            if self.state_family else 0
        )
        # The paged pools by name: the two of a window family, and the one
        # of a family whose EVERY layer holds K/V rows beside a state slot
        # (the parallel kind: its blocks and its slots are both a layer's),
        # as pool "full" (absent for every other family: its one pool is
        # xllm_engine_kv_cache_usage's).
        both_in_a_layer = PARALLEL_KIND in getattr(
            getattr(self.executor, "cfg", None), "layer_types", ()
        )
        if self.window_family or both_in_a_layer:
            live = self.metrics.gauge(
                "xllm_engine_kv_blocks_live",
                "Blocks of a paged pool owned by a sequence, by pool: the "
                "full layers' grow with the context, the window layers' "
                "are freed behind it",
                labelnames=("pool",),
            )
            live.labels(pool="full").set_function(
                lambda: self.block_mgr.num_referenced_blocks
            )
            if self.window_family:
                live.labels(pool="window").set_function(
                    lambda: self.block_mgr.window_blocks_live
                )
            size = self.metrics.gauge(
                "xllm_engine_kv_block_bytes",
                "Device bytes of one block of a paged pool over its layers, by pool",
                labelnames=("pool",),
            )
            size.labels(pool="full").set_function(
                lambda: self.executor.cache_row_bytes * self.block_size
            )
            if self.window_family:
                size.labels(pool="window").set_function(
                    lambda: self.executor._window_block_bytes()
                )
        # A family whose attention layers SELECT their pages
        # (ops/sparse_attention.py): token rows of a sparse layer by the
        # path their position sends them down, and for the selected rows
        # the pages read against the pages their contexts hold. Booked on
        # the host from the positions every dispatch already has.
        ecfg = getattr(self.executor, "cfg", None)
        self._sparse = None
        if getattr(ecfg, "num_sparse_layers", 0):
            self._sparse = (
                ecfg.sparse_dense_len, ecfg.sparse_topk, ecfg.sparse_block_size
            )
        self._sparse_counts = {
            "rows_selected": 0, "rows_dense": 0,
            "pages_selected": 0, "pages_live": 0,
        }
        for key, name, text in (
            ("rows_selected", "xllm_engine_attn_rows_selected_total",
             "Token rows of a sparse layer past sparse_dense_len: they "
             "attend the pages their query group selects"),
            ("rows_dense", "xllm_engine_attn_rows_dense_total",
             "Token rows of a sparse layer at or under sparse_dense_len: "
             "they attend their whole context"),
            ("pages_selected", "xllm_engine_sparse_pages_selected_total",
             "Pages the selected rows read, a KV head and sparse layer "
             "(sparse_topk a row)"),
            ("pages_live", "xllm_engine_sparse_pages_live_total",
             "Pages the selected rows' contexts hold (what a dense "
             "launch would read for them)"),
        ):
            self.metrics.counter(name, text).set_function(
                lambda key=key: self._sparse_counts[key]
            )
        self.metrics.counter(
            "xllm_engine_window_blocks_freed_total",
            "Window-pool blocks freed behind a running sequence (every "
            "position in them more than sliding_window behind its next)",
        ).set_function(
            lambda: getattr(self.block_mgr, "window_blocks_freed", 0)
        )
        self.metrics.counter(
            "xllm_engine_state_recomputes_total",
            "Preempted sequences of a state-pool family resumed by "
            "recomputing their tokens from position 0",
        ).set_function(lambda: self.state_recomputes)
        # Overlapped-pipeline instruments (docs/ENGINE_PIPELINE.md): the
        # host gap is the wall time between finishing one step's host
        # bookkeeping and dispatching the next decode step — the window the
        # device idles through at depth 0; depth 1 hides it behind the
        # in-flight step.
        self._m_host_gap = self.metrics.histogram(
            "xllm_engine_host_gap_ms",
            "Host bookkeeping gap between one decode step's drain and the "
            "next dispatch", buckets=LATENCY_BUCKETS_MS,
        )
        self.metrics.gauge(
            "xllm_engine_overlap_depth",
            "Decode steps currently in flight on the device (0 = idle or "
            "pipeline depth 0, 1 = one-step lookahead active)",
        ).set_function(lambda: 1 if self._inflight is not None else 0)
        self.metrics.counter(
            "xllm_engine_overlapped_steps_total",
            "Decode steps dispatched while the prior step was still in "
            "flight",
        ).set_function(lambda: self.overlap_steps)
        # Collective-overlap + dispatch-cache instruments (ISSUE 18,
        # docs/OBSERVABILITY.md): every fresh lowering past the prewarm
        # watermark is a miss (with no prewarm, ALL lowerings are). What
        # the persistent cache on disk did is another series:
        # xllm_engine_program_builds_total (obs/startup.py).
        self.metrics.counter(
            "xllm_engine_collective_overlap_steps_total",
            "Engine dispatches whose traced step programs carry the "
            "ring collective-matmul schedule (XLLM_OVERLAP_COLLECTIVES "
            "on a tp>1/ep>1 mesh)",
        ).set_function(lambda: self.collective_overlap_steps)
        self.metrics.counter(
            "xllm_engine_compile_cache_misses_total",
            "Entries the step programs' jit DISPATCH caches gained past "
            "the prewarm watermark (fresh lowerings: the "
            "first-post-idle-recompile class prewarm_programs exists to "
            "kill); not the persistent cache on disk, which is "
            "xllm_engine_program_builds_total",
        ).set_function(lambda: self.compile_cache_misses())
        self.metrics.counter(
            "xllm_engine_late_stop_discards_total",
            "In-flight sampled tokens discarded because their sequence "
            "stopped/cancelled/preempted one step earlier",
        ).set_function(lambda: self.late_stop_discards)
        self.metrics.counter(
            "xllm_engine_loop_errors_total",
            "Engine-loop iterations that raised (loop stays alive)",
        ).set_function(lambda: self.loop_errors)
        # Mixed step instruments (docs/KERNELS.md +
        # docs/OBSERVABILITY.md): how often the fused prefill+decode
        # dispatch runs and how it composes.
        self.metrics.counter(
            "xllm_engine_mixed_steps_total",
            "Engine steps that fused prefill chunk rows with the decode "
            "batch in one dispatch",
        ).set_function(lambda: self.mixed_steps)
        self._m_mixed_pf_rows = self.metrics.histogram(
            "xllm_engine_mixed_batch_prefill_rows",
            "Prefill chunk rows per mixed dispatch",
            buckets=BATCH_BUCKETS,
        )
        self._m_mixed_dec_rows = self.metrics.histogram(
            "xllm_engine_mixed_batch_decode_rows",
            "Active decode slots per mixed dispatch",
            buckets=BATCH_BUCKETS,
        )
        # Composed-path instruments (ISSUE 13, docs/ENGINE_PIPELINE.md):
        # speculative verify inside the overlapped pipeline + in-graph
        # guided masking, with the per-slot fallback counters.
        self._m_spec_accepted = self.metrics.histogram(
            "xllm_engine_spec_accepted_len",
            "Tokens emitted per slot per speculative verify step "
            "(accepted prefix + the corrected/bonus token)",
            buckets=BATCH_BUCKETS,
        )
        self.metrics.counter(
            "xllm_engine_spec_pipeline_steps_total",
            "Speculative verify steps dispatched through the overlapped "
            "pipeline (device-resident accepted-token feedback)",
        ).set_function(lambda: self.spec_pipeline_steps)
        self.metrics.counter(
            "xllm_engine_spec_sync_steps_total",
            "Speculative verify steps drained at pipeline depth 0 "
            "(sync_engine)",
        ).set_function(lambda: self.spec_sync_steps)
        self.metrics.counter(
            "xllm_engine_guided_ingraph_steps_total",
            "Pipelined dispatches that applied at least one guided mask "
            "row in-graph (no engine flush)",
        ).set_function(lambda: self.guided_ingraph_steps)
        self.metrics.counter(
            "xllm_engine_guided_paced_skips_total",
            "Guided slots held out of one pipelined dispatch so their "
            "next mask row derives from the exact host automaton state "
            "(the per-slot — not per-engine — fallback)",
        ).set_function(lambda: self.guided_paced_skips)
        # Resolved attention-dispatch accounting: which kernel actually
        # served each engine dispatch (the env var alone told the record
        # nothing — ISSUE 9). Names resolve once at engine build from the
        # executor's cache/geometry (kernel choices are process-static:
        # the jitted steps bake them in at first trace).
        self._m_kernel_dispatch = self.metrics.counter(
            "xllm_engine_kernel_dispatch_total",
            "Engine device dispatches by resolved attention kernel",
            labelnames=("kernel",),
        )
        rep = (
            self.executor.kernel_report()
            if hasattr(self.executor, "kernel_report") else {}
        )
        self._kernel_names = {
            "decode": rep.get("decode", "unknown"),
            "prefill": rep.get("prefill", "unknown"),
            "mq": rep.get("mq", "unknown"),
            # the pair a fused step launches side by side: "paged+flash"
            "mixed": rep.get("mixed", "unknown"),
        }
        self.metrics.counter(
            "xllm_engine_kv_chunk_land_errors_total",
            "Streamed PD chunks that failed to land into the prefix "
            "cache after being acked (their span recomputes at commit)",
        ).set_function(lambda: self.kv_chunk_land_errors)
        self.metrics.counter(
            "xllm_engine_preemptions_total",
            "Recompute-style preemptions (pool pressure + hybrid "
            "eviction)",
        ).set_function(lambda: self.preemptions)
        self.metrics.counter(
            "xllm_engine_prefix_cached_tokens_total",
            "Prompt tokens served from the prefix cache at admission",
        ).set_function(lambda: self.prefix_cached_tokens)
        self.metrics.counter(
            "xllm_engine_prefix_prompt_tokens_total",
            "Prompt tokens eligible for prefix-cache matching",
        ).set_function(lambda: self.prefix_prompt_tokens)
        self.metrics.counter(
            "xllm_engine_midprefill_rematch_blocks_total",
            "KV blocks adopted at a chunk boundary after landing "
            "mid-prefill (fabric fetches, streamed PD chunks, sibling "
            "commits)",
        ).set_function(lambda: self.midprefill_adopted_blocks)
        # NO waiting-depth / KV-usage gauges here: the instance front door
        # already exports those via get_load_metrics (they would duplicate
        # xllm_engine_waiting_requests / xllm_engine_kv_cache_usage in the
        # same merged exposition).
        self.metrics.gauge(
            "xllm_engine_running_requests", "Sequences holding decode "
            "slots",
        ).set_function(lambda: len(self._running))
        self.metrics.counter(
            "xllm_engine_block_evictions_total",
            "Committed blocks evicted from the device pool",
        ).set_function(lambda: getattr(self.block_mgr, "evictions_total", 0))
        self.metrics.counter(
            "xllm_engine_host_cache_hits_total",
            "Host (DRAM) tier prefix-block hits",
        ).set_function(
            lambda: getattr(self.host_pool, "hits", 0)
            if self.host_pool is not None else 0
        )
        self.metrics.counter(
            "xllm_engine_host_cache_misses_total",
            "Host (DRAM) tier lookups that missed",
        ).set_function(
            lambda: getattr(self.host_pool, "misses", 0)
            if self.host_pool is not None else 0
        )
        self.metrics.counter(
            "xllm_engine_host_cache_evictions_total",
            "Blocks LRU-evicted from the host (DRAM) tier",
        ).set_function(
            lambda: getattr(self.host_pool, "evictions", 0)
            if self.host_pool is not None else 0
        )
        # Grouped-MoE dispatch instruments (docs/MOE.md +
        # docs/OBSERVABILITY.md): expert load and where the pairs fell,
        # pull-only from the executor's accumulators (booked from the
        # step programs' own output at each drain). The
        # hot-expert share doubles as the per-instance load signal the
        # master's routing reads next to cache hits
        # (LoadMetrics.moe_hot_expert_frac).
        ex = self.executor
        if getattr(getattr(ex, "cfg", None), "is_moe", False) and hasattr(
            ex, "moe_stats"
        ):
            # One moe_stats() snapshot serves the whole scrape: the
            # scalar metrics plus num_experts gauge children would
            # otherwise re-lock and copy the counts array N+3 times per
            # render (256 experts on a V3-class config). 0.25 s staleness
            # is invisible at scrape cadence; dict swaps are GIL-atomic.
            _memo = {"t": 0.0, "s": None}

            def _snap():
                now = time.monotonic()
                if _memo["s"] is None or now - _memo["t"] > 0.25:
                    _memo["s"] = ex.moe_stats()
                    _memo["t"] = now
                return _memo["s"]

            self.metrics.counter(
                "xllm_engine_moe_assignments_total",
                "Routed (token, expert) pairs the router made, summed "
                "over layers (held and absent experts alike)",
            ).set_function(lambda: _snap()["assignments"])
            pairs = self.metrics.counter(
                "xllm_engine_moe_pairs_total",
                "Routed pairs by where their expert is: held by this "
                "instance (computed here) or absent (another holder's)",
                labelnames=("where",),
            )
            pairs.labels(where="held").set_function(lambda: _snap()["held"])
            pairs.labels(where="absent").set_function(
                lambda: _snap()["absent"]
            )
            self.metrics.counter(
                "xllm_engine_moe_experts_touched_total",
                "Held experts a layer's tokens touched, summed over "
                "layers and steps: each is one expert's weights read",
            ).set_function(lambda: _snap()["touched"])
            self.metrics.counter(
                "xllm_engine_moe_experts_held_total",
                "Held experts x expert layers, summed over step program "
                "runs: what xllm_engine_moe_experts_touched_total is a "
                "share of (touched / held = the share of the held experts' "
                "weights a layer and step streams)",
            ).set_function(lambda: _snap()["held_reads"])
            self.metrics.counter(
                "xllm_engine_moe_dropped_total",
                "Pairs of a held expert that were not computed: 0 by "
                "construction (the grouped product has no capacity); a "
                "run in which it moves is a finding",
            ).set_function(lambda: _snap()["dropped"])
            self.metrics.gauge(
                "xllm_engine_moe_hot_expert_frac",
                "Hottest expert's share of routed assignments "
                "(cumulative; 1/num_experts = perfectly balanced)",
            ).set_function(lambda: _snap()["hot_expert_frac"])
            g = self.metrics.gauge(
                "xllm_engine_moe_expert_load",
                "Per-expert share of routed assignments (cumulative)",
                labelnames=("expert",),
            )
            for i in range(int(ex.moe_stats()["experts"])):
                def _share(i=i):
                    s = _snap()
                    return (
                        float(s["expert_counts"][i]) / s["assignments"]
                        if s["assignments"] else 0.0
                    )
                g.labels(expert=str(i)).set_function(_share)

    # -------------------------------------------------------------- public

    def _observe_batch(self, nactive: int) -> None:
        self._m_batch.observe(nactive)
        if self.state_family:
            self._m_state_in_use.observe(self.R - len(self._free_slots))

    def _book_sparse_rows(self, positions: np.ndarray) -> None:
        """Rows a dispatch sends through the sparse layers, by path."""
        if self._sparse is None or not len(positions):
            return
        dense_len, topk, bs = self._sparse
        ctx = np.asarray(positions, np.int64) + 1
        past = ctx > dense_len
        n = int(past.sum())
        c = self._sparse_counts
        c["rows_selected"] += n
        c["rows_dense"] += len(ctx) - n
        c["pages_selected"] += n * topk
        c["pages_live"] += int((-(-ctx[past] // bs)).sum())

    def _no_state_handoff(self) -> None:
        if self.state_family or self.window_family:
            self.executor._no_state_handoff()  # raises, by name

    @thread_owned("engine")
    def _table(self, seq: _Seq, first: int, end: int) -> np.ndarray:
        """A sequence's block table as a PrefillItem takes it, for a step
        that writes positions [first, end). A window family's is twice as
        wide: the window pool's columns behind the full pool's, slid to
        the blocks this step's queries can see (`_slide_window`)."""
        table = np.zeros((self._block_tables.shape[1],), np.int32)
        table[: len(seq.block_ids)] = seq.block_ids
        self._book_sparse_rows(np.arange(first, end))
        if self.window_family:
            self._slide_window(seq, first, end, table[self.max_blocks:])
        return table

    @thread_owned("engine")
    def _slide_window(self, seq: _Seq, first: int, end: int, row) -> None:
        """Window family: make live the window-pool blocks that a step
        writing positions [first, end) of `seq` reads or writes (a query
        at p sees j > p - window), free the ones wholly behind them, and
        write the sequence's window table into `row`. The window pool is
        sized so that this cannot run out (executor._decide_window_blocks)."""
        bs = self.block_size
        lo = max(0, first - self.window + 1) // bs
        hi = (max(end, first + 1) - 1) // bs + 1
        seq.window_lo = self.block_mgr.slide(
            seq.block_ids, seq.window_lo, lo, hi, row
        )

    def add_request(self, req: EngineRequest) -> None:
        if req.prefill_only:
            self._no_state_handoff()
        req.queued_at = time.monotonic()
        with self._lock:
            self._waiting.append(req)
        self._work.set()

    def wake(self) -> None:
        """External work signal (streamed mm chunk landed, etc.): a
        request parked at an admission gate re-checks without waiting
        out the loop's idle poll."""
        self._work.set()

    def cancel(self, request_id: str) -> None:
        with self._lock:
            self._cancelled.add(request_id)
        self._work.set()

    def add_step_listener(self, fn) -> None:
        """Register `fn()`, to run on the stepping thread after a step's
        booking has made its last callback (`_book_step`, the prefill
        rows of a mixed step included) and at the end of every `step()`
        in which a callback ran outside a booking (a reject at admission,
        a cancel notice, the split prefill's first tokens): no output
        waits for a later step. Register before `start()`. A listener
        that raises is logged and the loop lives; what it held is its
        owner's to account for (docs/ENGINE_PIPELINE.md)."""
        self._step_listeners.append(fn)

    def step_open(self) -> bool:
        """Whether the caller is the stepping thread inside a `step()`
        whose end runs the listeners: what a callback that only collects
        asks before it leaves the rest to its listener."""
        return self._step_thread == threading.get_ident()

    def has_work(self) -> bool:
        return bool(
            self._waiting
            or self._running
            or self._pf_active
            or self._pending_imports
            or self._pending_kv_chunks
            or self._pending_exports
            or self._inflight is not None
        )

    def compile_cache_misses(self) -> int:
        """Fresh lowerings past the executor's prewarm watermark (every
        lowering when nothing was prewarmed)."""
        ex = self.executor
        count = getattr(ex, "lowering_count", None)
        if count is None:
            return 0
        return max(0, count() - getattr(ex, "prewarmed_lowerings", 0))

    def _mark_first_step(self) -> None:
        """Arm `xllm_engine_first_step_seconds`: the first `device_wait`
        the engine thread books sets it and takes this hook out again, so
        no later step pays for it."""
        phase_inc = self._phase_inc  # the hook holds the table, not the engine
        inc = phase_inc["device_wait"]

        def first(dt: float) -> None:
            phase_inc["device_wait"] = inc
            inc(dt)
            obs_startup.TIMELINE.mark_first_step()

        phase_inc["device_wait"] = first

    def start(self) -> None:
        if self.cfg.warmup_on_start and hasattr(self.executor, "warmup"):
            # With a persistent cache dir configured, walk the
            # FULL bucket-program family (runtime/compile_cache.py) so
            # no first-post-idle dispatch ever lowers fresh — the disk
            # cache amortizes the enumeration across restarts. Without
            # a dir the full walk would pay its whole compile bill
            # every start, so keep the classic split-step warmup.
            with obs_startup.startup_phase("programs"):
                if compile_cache_mod.resolve_cache_dir(
                    self.cfg.compilation_cache_dir
                ) and hasattr(self.executor, "prewarm_programs"):
                    self.executor.prewarm_programs()
                else:
                    self.executor.warmup()
        self._mark_first_step()  # after the warm-up's own reads
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop = True
        self._work.set()
        if self._thread:
            self._thread.join(timeout=10)
        if self.ssd_pool is not None:
            self.ssd_pool.close()

    # ------------------------------------------------------------- metrics

    def get_load_metrics(self) -> LoadMetrics:
        # Expert hotness rides the heartbeat-visible load snapshot so the
        # master can weigh MoE routing skew next to cache hits (ISSUE 15;
        # 0.0 for dense models / grouped dispatch off — the field is
        # inert). The read is scrape-safe: it never drains the pipeline.
        moe_frac = 0.0
        ex = self.executor
        if getattr(getattr(ex, "cfg", None), "is_moe", False) and hasattr(
            ex, "moe_stats"
        ):
            moe_frac = float(ex.moe_stats()["hot_expert_frac"])
        return LoadMetrics(
            waiting_requests_num=len(self._waiting),
            gpu_cache_usage_perc=self.block_mgr.usage,
            moe_hot_expert_frac=moe_frac,
        )

    def get_latency_metrics(self, window_s: float = 30.0) -> LatencyMetrics:
        """Heartbeat / scrape threads: never mutates the windows. tuple(dq)
        copies under the GIL in one C call, so the engine thread's appends
        cannot fail it (a Python-level iteration of the live deque did:
        "deque mutated during iteration" killed the heartbeat)."""
        horizon = time.monotonic() - window_s

        def recent_max(dq) -> int:
            return int(
                max((v for t, v in tuple(dq) if t >= horizon), default=0)
            )

        return LatencyMetrics(
            recent_max_ttft=recent_max(self._ttft_window),
            recent_max_tbt=recent_max(self._tbt_window),
        )

    def _window_append(self, dq, now: float, ms: float) -> None:
        """Engine thread: one latency-window sample, old ones trimmed."""
        dq.append((now, ms))
        while now - dq[0][0] > LATENCY_WINDOW_S:
            dq.popleft()

    def _profile_step(self, nactive: int, total_ctx: int, ms: float) -> None:
        if len(self._profile_tpot) < PROFILE_SAMPLES:
            self._profile_tpot.append((nactive, total_ctx, ms))

    def take_cache_event(self) -> KvCacheEvent:
        return self.block_mgr.take_cache_event()

    def cache_snapshot(self) -> list:
        """Every committed prefix-cache block hash — the takeover
        reconciliation manifest (POST /reconcile) and the fabric's
        post-ejection heartbeat cache resync. Racy read by design: a hash
        that commits or evicts mid-snapshot merely drifts the master's
        index by one heartbeat (both block managers retry the rare
        resize-during-iteration internally)."""
        fn = getattr(self.block_mgr, "committed_hashes", None)
        return fn() if callable(fn) else []

    def cache_snapshot_event(self) -> KvCacheEvent:
        """Full-tier cache snapshot as a KvCacheEvent — the heartbeat
        cache RESYNC payload after the master pruned this instance's
        index locations (breaker ejection): HBM commits as stored, host/
        SSD holdings as offload entries, so every tier's locations
        rebuild, not just the hot one. Racy off-thread reads like
        cache_snapshot: one-beat drift is the contract."""
        stored = set(self.cache_snapshot())
        offload: Dict[bytes, str] = {}
        for pool, tier in ((self.host_pool, "dram"), (self.ssd_pool, "ssd")):
            if pool is None:
                continue
            for h in pool.hashes():
                if h not in stored and h not in offload:
                    offload[h] = tier
        return KvCacheEvent(stored_cache=stored, offload_cache=offload)

    def profiling_data(self):
        return list(self._profile_ttft), list(self._profile_tpot)

    # ---------------------------------------------------------------- loop

    def _loop(self) -> None:
        # This thread owns the slot arrays, block manager, and host/SSD
        # pools until the loop exits (docs/STATIC_ANALYSIS.md): the
        # @thread_owned("engine") surfaces runtime-assert it under
        # XLLM_THREAD_CHECKS=1, and graftlint's thread-ownership pass
        # checks their call sites statically.
        claim_thread(self, "engine")
        try:
            _on_roomy_stack(self._loop_owned)
        finally:
            release_thread(self, "engine")

    @thread_owned("engine")
    def _loop_owned(self) -> None:
        log = logging.getLogger(__name__)
        phase = self._phases.phase
        # The loop's own lines (has_work, the error path) are housekeeping;
        # every phase step() enters suspends it, so the phases partition
        # the loop's time (obs.spans.EnginePhases).
        with phase("housekeeping"):
            while not self._stop:
                if not self.has_work():
                    with phase("idle"):
                        self._work.wait(timeout=0.05)
                    self._work.clear()
                    continue
                try:
                    produced = self.step()
                    if produced == 0 and self._inflight is None:
                        # Waiting work that cannot run yet (e.g. blocked on
                        # KV capacity): sleep on the work event — set when
                        # KV blocks are freed (_finish), imports/cancels
                        # land, or new requests arrive — instead of a blind
                        # busy-backoff.
                        with phase("idle"):
                            self._work.wait(timeout=0.05)
                        self._work.clear()
                except Exception:  # pragma: no cover — keep the loop alive
                    self.loop_errors += 1
                    log.exception("engine loop iteration failed")
                    time.sleep(0.1)

    # ---------------------------------------------------------------- step

    @property
    def _force_sync(self) -> bool:
        """Pipeline depth 0 (cfg.sync_engine), read LIVE every step: a
        flip on a running engine takes effect at the next iteration, and
        step() flushes what the pipeline held at the transition. Guided
        sequences do not appear here: they ride the pipeline host-paced
        (per-slot, see _apply_guided_pacing)."""
        return self.cfg.sync_engine

    @property
    def mixed_step_enabled(self) -> bool:
        """Whether due prefill chunks ride this iteration's dispatch
        (docs/KERNELS.md) — live, the one decision prewarm shares."""
        return fuses_prefill(self.cfg, self.executor)

    @thread_owned("engine")
    def step(self) -> int:
        """One engine iteration, the only one: land migrated KV, schedule
        (continue chunks and admit), dispatch one decode or speculative
        verify step, drain one. Returns number of tokens produced.

        Depth 1 (default): the dispatch for step N+1 happens BEFORE step
        N's results are consumed, so host bookkeeping runs while the
        device computes — for plain decode AND speculative verify (step
        N+1's verify inputs are gathered on-device from step N's
        variable accepted counts). Guided sequences ride the pipeline
        host-paced per slot. Depth 0 (sync_engine) is the same loop
        draining the step it just dispatched: every slot host-fed, no
        late-stop discard, prefill split, drafts proposed from current
        history. With `mixed_step_enabled` the due prefill chunks
        (continuations first — they hold slots and blocks — then fresh
        admissions) ride the dispatch FUSED with the decode or verify
        rows; ineligible admissions (media / SP) and every admission of
        an unfused iteration prefill through the split path inside
        _admit (docs/ENGINE_PIPELINE.md + docs/KERNELS.md)."""
        self._step_thread = threading.get_ident()
        try:
            return self._step()
        finally:
            # A raise in mid-step hands over what was booked before it.
            self._step_boundary()
            self._step_thread = None

    @thread_owned("engine")
    def _step_boundary(self) -> None:
        """Run the step listeners if any callback ran since they last
        did: the end of a booking, and the end of step()."""
        if not self._unhanded:
            return
        self._unhanded = False
        for fn in self._step_listeners:
            try:
                fn()
            except Exception:  # as a raising callback: the loop lives
                logging.getLogger(__name__).exception(
                    "engine step listener failed"
                )

    @thread_owned("engine")
    def _step(self) -> int:
        """step()'s body; step() marks the stepping thread around it and
        runs the step listeners once more when it is done."""
        phase = self._phases.phase
        # up to the first phase below: the loop's `housekeeping`
        if not self._running and self._inflight is None:
            self._t_host_free = None  # idle time is not a host gap
        self._drain_imports()
        self._drain_export_requests()
        self._drain_cancelled()
        self._maybe_flush_schema_rows()
        sync = self._force_sync
        fuse = self.mixed_step_enabled
        produced = 0
        if sync or (self._pf_active and not fuse):
            # Transition (depth flipped to 0, or fusing turned off with
            # seqs mid-prefill): drain the in-flight step and requeue
            # the mixed-held seqs into the split midchunk flow.
            produced = self._flush_pipeline_state()
        items_meta: List[tuple] = []
        with phase("schedule"):
            if fuse:
                budget = self._continue_pf_chunks(
                    items_meta, self.cfg.max_prefill_tokens
                )
                produced += self._admit(
                    mixed_collect=items_meta, budget=budget
                )
            else:
                produced += self._admit()
        with phase("dispatch"):
            if self.cfg.speculative_tokens > 0:
                nxt = self._dispatch_verify(items_meta)
            else:
                nxt = self._dispatch(items_meta)
        if sync:
            return produced + self._drain_step(nxt, None)
        produced += self._drain_step(self._inflight, nxt)
        self._inflight = nxt
        return produced

    @thread_owned("engine")
    def _flush_inflight(self) -> int:
        """Drain any in-flight step without dispatching a successor (mode
        transitions and shutdown): surviving slots return to host feeding."""
        produced = self._drain_step(self._inflight, None)
        self._inflight = None
        return produced

    @thread_owned("engine")
    def _flush_pipeline_state(self) -> int:
        """Mode-transition flush: drain the in-flight step AND hand any
        mixed-held mid-prefill seqs back to the split midchunk flow —
        they keep slot + blocks and continue FIRST, like any split-mode
        mid-chunk seq. One implementation for every transition (depth
        flipped to 0, mixed-off flip, spec fuse-support flip)."""
        produced = self._flush_inflight()
        if self._pf_active:
            with self._lock:
                self._waiting.extendleft(
                    reversed(list(self._pf_active.values()))
                )
            self._pf_active.clear()
        return produced

    # --------------------------------------------------------- mixed step

    @thread_owned("engine")
    def _continue_pf_chunks(self, items_meta: List[tuple],
                            budget: int) -> int:
        """Cut the next chunk for every mid-prefill seq (_pf_active) with
        tokens left to dispatch. Back-to-back chunks PIPELINE: chunk c+1
        is cut from `pf_dispatched` (the dispatched extent) while chunk c
        is still in flight, so chunked prefill advances every iteration
        like split mode — drain-side bookkeeping (`prefilled`, KV
        streaming, finish) stays one step behind. The chunk-boundary
        cache re-match runs at the DISPATCHED frontier even while a
        chunk is in flight — in-flight chunks only write below the
        frontier, so frontier-aligned adoption never touches their
        blocks (see the call-site comment and _extend_midchunk_match).

        One mixed dispatch carries ONE padded-length bucket (the first
        due chunk's), exactly like _prefill_group's same-bucket grouping:
        a prefill row's numerics are only byte-stable at a fixed Lpad, so
        padding a short chunk to a longer peer's bucket would break
        mixed ≡ split parity (docs/KERNELS.md). Mismatched seqs stop the
        walk (FIFO head-of-line, like the split queue) and ride the next
        iteration's dispatch."""
        group_max = getattr(self.executor, "PREFILL_GROUP_MAX", 8)
        bucket = None
        for seq in list(self._pf_active.values()):
            if budget <= 0 or len(items_meta) >= group_max:
                break
            if seq.pf_dispatched >= len(seq.tokens):
                continue  # final chunk in flight; waiting on its drain
            # Adopt blocks that landed since the last boundary (fabric
            # fetch, streamed PD chunk, sibling commit) at the DISPATCHED
            # frontier — live even while a chunk is in flight (the chunk
            # writes only below the frontier). The hash chain covers at
            # most tokens[:n-1], so at least the final token always
            # remains to dispatch.
            seq.pf_dispatched += self._extend_midchunk_match(
                seq, frontier=seq.pf_dispatched
            )
            chunk = min(len(seq.tokens) - seq.pf_dispatched, budget)
            b = self.executor.bucket_len(chunk)
            if bucket is None:
                bucket = b
            elif b != bucket:
                break
            items_meta.append((seq, seq.pf_dispatched, chunk))
            budget -= chunk
        return budget

    @thread_owned("engine")
    def _build_pf_items(self, items_meta: List[tuple], t0: float):
        """PrefillItems + drain entries for the due chunks riding a
        fused dispatch (shared by _dispatch and _dispatch_verify).
        Guided seqs' FINAL chunks carry their host-derived mask row —
        exact at dispatch, because a mid-prefill seq has no decode step
        in flight (its automaton state is host truth)."""
        from xllm_service_tpu.runtime.executor import PrefillItem

        items = []
        pf_entries = []
        for j, (seq, start, n) in enumerate(items_meta):
            s = seq.req.sampling
            table = self._table(seq, start, start + n)
            final = start + n >= len(seq.tokens)
            # First chunk: TTFT base. The unset check (0.0 = never set)
            # covers a deferred first chunk whose start moved past
            # num_cached via frontier adoption before it dispatched.
            queued_ms = None
            if start <= seq.num_cached or seq.prefill_start_time == 0.0:
                queued_ms = self._first_dispatch(seq, t0)
            items.append(PrefillItem(
                token_ids=np.asarray(seq.tokens[start:start + n], np.int32),
                start_pos=start,
                block_table=table,
                slot=seq.slot,
                temperature=s.temperature,
                top_k=s.top_k,
                top_p=s.top_p,
                seed=s.seed,
                step=len(seq.generated),
                presence=getattr(s, "presence_penalty", 0.0),
                frequency=getattr(s, "frequency_penalty", 0.0),
                # Final-chunk-only sampling features, exactly like the
                # split path (_prefill_admitted): intermediate chunks'
                # sampled tokens are discarded.
                logit_bias=(
                    tuple(getattr(s, "logit_bias", ()) or ())
                    if final else ()
                ),
                mask_row=(
                    self._guided_row(seq)
                    if final and seq.req.guided
                    and self._guided_tokens is not None
                    else -1
                ),
                adapter_idx=seq.req.adapter_idx,
                min_p=getattr(s, "min_p", 0.0) if final else 0.0,
                prior_tokens=(
                    np.asarray([t for t, _ in seq.generated], np.int32)
                    if seq.generated and final
                    and (
                        getattr(s, "presence_penalty", 0.0)
                        or getattr(s, "frequency_penalty", 0.0)
                    )
                    else None
                ),
            ))
            pf_entries.append(
                (seq, seq.admit_gen, j, start, start + n, queued_ms)
            )
            seq.pf_dispatched = start + n
            self.prefill_tokens += n
        self.prefill_chunks += len(items)
        return items, pf_entries

    def _first_dispatch(self, seq: "_Seq", t0: float) -> Optional[float]:
        """A first prefill chunk dispatches at t0: the TTFT base and,
        once per request that came through add_request, the engine's
        queue wait (returned in ms; None for a resumed sequence)."""
        seq.prefill_start_time = t0
        req = seq.req
        if not req.queued_at:
            return None
        queued_ms = (t0 - req.queued_at) * 1000
        req.queued_at = 0.0
        self._m_queue_wait.observe(queued_ms)
        return queued_ms

    @thread_owned("engine")
    def _apply_guided_pacing(self, can: np.ndarray) -> np.ndarray:
        """Per-slot guided pipeline rule (docs/ENGINE_PIPELINE.md): a
        guided slot joins a dispatch only when NO step of its own is in
        flight, so its mask row derives from the EXACT host automaton
        state (which has consumed every emitted token). The slot runs
        host-paced — every other pipeline iteration — instead of
        flushing the whole engine; unguided slots are unaffected."""
        for slot in self._guided_slots:
            if can[slot] and self._ps_pending[slot] > 0:
                can[slot] = False
                self.guided_paced_skips += 1
        return can

    def _guided_mask_rows(self, can: np.ndarray) -> Optional[np.ndarray]:
        """[R] mask-table rows for the guided slots riding this dispatch
        (None when none do). Dispatched guided slots are always
        host-paced fresh, so _guided_row sees the exact state."""
        if self._guided_tokens is None or not self._guided_slots:
            return None
        rows = None
        for slot in self._guided_slots:
            if can[slot]:
                if rows is None:
                    rows = np.full(
                        (self.R,), self.executor.permissive_row, np.int32
                    )
                rows[slot] = self._guided_row(self._running[slot])
        return rows

    @thread_owned("engine")
    def _dispatch(self, items_meta: List[tuple]) -> Optional[_InFlight]:
        """Dispatch the next decode step, returning its in-flight record:
        fused with the due prefill chunks as ONE device step
        (executor.mixed_start) when `items_meta` holds any — the fused
        shapes only compile when a mixed batch actually exists — else a
        plain decode step (executor.decode_start; None when no row can
        run). Continuing slots feed from the PREVIOUS step's
        device-resident sampled tokens — the autoregressive feedback
        never round-trips the host. Freshly admitted/resumed slots, and
        every slot at depth 0, feed from the host array.
        Length-predictable stops (max_new_tokens / max_seq_len) are
        excluded up front; the token-dependent ones (EOS / stop ids)
        surface at drain, one step late at depth 1, and cost exactly one
        discarded sample."""
        if not items_meta and not self._running:
            return None
        can = (
            self._ps_active
            & (self._ps_gen_count + self._ps_pending < self._ps_max_new)
            & (
                self._ps_tok_count + self._ps_pending
                < self.cfg.max_seq_len
            )
        )
        can = self._apply_guided_pacing(can)
        if can.any():
            self._ensure_decode_capacity(1, mask=can)
            can &= self._ps_active  # the capacity pass may have preempted
        if not items_meta and not can.any():
            return None
        batch = self._sampling_batch_view()
        rows = self._guided_mask_rows(can)
        if rows is not None:
            batch.mask_rows = rows
            self.guided_ingraph_steps += 1
        prev = self._inflight
        # Non-dispatched rows read the (defined) host value; dispatched
        # rows read the device feedback unless freshly (re)admitted.
        fresh_mask = self._fresh | ~can
        # Invariant: a non-fresh dispatched slot's feed lives in the
        # in-flight step — with no in-flight step every slot is host-fed.
        assert prev is not None or bool(fresh_mask[can].all())
        self._observe_host_gap()
        t0 = time.monotonic()
        items, pf_entries = self._build_pf_items(items_meta, t0)
        prev_tokens = prev.feed if prev is not None else None
        feed = None
        # annotate=False: the executor's leaf annotations stay leaves
        with self._phases.phase("dispatch", annotate=False):
            if items:
                tokens, logprobs, feed = self.executor.mixed_start(
                    items,
                    self._ps_last_tok,
                    fresh_mask,
                    prev_tokens,
                    self._ps_positions,
                    self._block_tables,
                    can,
                    batch,
                )
            else:
                tokens, logprobs = self.executor.decode_start(
                    self._ps_last_tok,
                    fresh_mask,
                    prev_tokens,
                    self._ps_positions,
                    self._block_tables,
                    can,
                    batch,
                )
        snapshot, nactive, total_ctx = self._snapshot_dispatch(
            can, len(items), "mixed" if items else "decode"
        )
        self._ps_positions[can] += 1
        self._ps_steps[can] += 1
        return _InFlight(
            tokens, logprobs, snapshot, t0, nactive, total_ctx,
            pf=pf_entries, feed=feed, moe=self._take_moe(),
        )

    def _take_moe(self):
        take = getattr(self.executor, "take_moe_stats", None)
        return take() if take is not None else ()

    @thread_owned("engine")
    def _snapshot_dispatch(self, can: np.ndarray, n_pf: int, kernel: str):
        """What every dispatch (decode, mixed, verify) books once its
        step is launched: the slot -> (seq, admit_gen) snapshot its drain
        checks occupancy against, the pending/fresh advance and the
        counters. Returns (snapshot, nactive, total_ctx); positions and
        step counts are the caller's (a verify step's advance is
        variable and re-derived at drain)."""
        nactive = int(can.sum())
        total_ctx = int(self._ps_positions[can].sum()) + nactive
        self._book_sparse_rows(self._ps_positions[can])
        snapshot = {}
        for slot in np.nonzero(can)[0]:
            seq = self._running[int(slot)]
            snapshot[int(slot)] = (seq, seq.admit_gen)
        self._ps_pending[can] += 1
        self._fresh[can] = False
        self._observe_batch(nactive)
        self._m_steps.inc()
        slots, live, drawn = self._sample_rows_inc
        slots(self.R)
        live(nactive)
        drawn(int(np.count_nonzero(can & (self._ps_temps > 0))))
        self.decode_dispatches += 1
        self.collective_overlap_steps += self._overlap_collectives
        if n_pf:
            self.mixed_steps += 1
            self._m_mixed_pf_rows.observe(n_pf)
            self._m_mixed_dec_rows.observe(nactive)
        self._m_kernel_dispatch.labels(
            kernel=self._kernel_names[kernel]
        ).inc()
        if self._inflight is not None:
            self.overlap_steps += 1
        return snapshot, nactive, total_ctx

    # ------------------------------------------------------------ admission

    @staticmethod
    def _item_req(item) -> EngineRequest:
        return item.req if isinstance(item, _Seq) else item

    @thread_owned("engine")
    def _drain_cancelled(self) -> None:
        dropped = []
        with self._lock:
            cancelled = self._cancelled
            self._cancelled = set()
            if not cancelled:
                return
            kept: Deque = collections.deque()
            for item in self._waiting:
                if self._item_req(item).request_id in cancelled:
                    dropped.append(item)
                else:
                    kept.append(item)
            self._waiting = kept
        for item in dropped:
            # A mid-chunk seq waits HOLDING its slot and blocks — release
            # both (ordinary waiting items hold neither).
            if isinstance(item, _Seq) and item.block_ids:
                self.block_mgr.free(item.block_ids)
                item.block_ids = []
                self._free_slots.append(item.slot)
            self._notify_cancelled(self._item_req(item))
        # Mixed-step mid-prefill seqs hold slot + blocks in _pf_active:
        # release both; any chunk still in flight for them drains to a
        # discard (the pf identity check below misses on the removed
        # entry) — the freed blocks' device writes are ordered before any
        # re-user's, exactly the late-stop-discard argument.
        for rid, seq in list(self._pf_active.items()):
            if rid in cancelled:
                del self._pf_active[rid]
                self.block_mgr.free(seq.block_ids)
                seq.block_ids = []
                self._free_slots.append(seq.slot)
                self._notify_cancelled(seq.req)
        for slot, seq in list(self._running.items()):
            if seq.req.request_id in cancelled:
                self._finish(seq, FinishReason.NONE, cancelled=True)

    @thread_owned("engine")
    def _admit(self, mixed_collect=None, budget=None) -> int:
        """Admit waiting requests up to max_prefill_tokens and prefill them
        in BATCHED compiled steps (executor.prefill_batch groups by length
        bucket) — one slow prefill no longer serializes the whole queue and
        concurrent short prompts share a single device step (round-1 weak
        item 4).

        Mixed stepping passes `mixed_collect`: freshly admitted
        seqs ELIGIBLE for the fused step (plain text — no media/stream,
        no guided mask, no SP-ring routing) are appended there (and
        registered in _pf_active) instead of prefilling here; their
        chunks ride the SAME dispatch as the decode batch
        (_dispatch / _dispatch_verify). Ineligible requests keep the
        split prefill path below, in the same iteration."""
        if budget is None:
            budget = self.cfg.max_prefill_tokens
        pool_capacity = self.block_mgr.num_blocks - 1
        rejects: List[Tuple[EngineRequest, StatusCode, str]] = []
        batch: List[_Seq] = []
        # Full-block hashes the CURRENT batch will commit. A waiting request
        # sharing a prefix with an in-batch member (chained hashes: any
        # overlap implies block-0 overlap) is deferred one step so it
        # prefix-matches the committed blocks instead of redundantly
        # prefilling the shared prefix in the same batched step.
        pending_hashes: set = set()

        # Streamed-media requests deferred this round (embeddings for
        # their next chunk still in flight): re-fronted after the scan so
        # they never head-of-line-block text traffic behind them.
        deferred: List = []

        # Mid-chunk seqs continue FIRST, wherever they sit in the queue: a
        # preempted/blocked item appendleft'd in front of one must not
        # starve it — it HOLDS slot + blocks that only further chunks can
        # turn into output (it is not in _running, so it is neither
        # preemptible nor evictable; skipping it could deadlock the pool).
        with self._lock:
            midchunk = [
                x
                for x in self._waiting
                if isinstance(x, _Seq) and x.block_ids
            ]
            for x in midchunk:
                self._waiting.remove(x)
        for seq in midchunk:
            if seq.req.mm_stream is not None:
                # Streamed encoder handoff (docs/EPD.md): the next chunk
                # may only run once every placeholder it covers has
                # landed — text-only chunks before the first uncovered
                # placeholder keep prefilling while the encoder streams.
                pos_end = seq.prefilled + min(
                    len(seq.tokens) - seq.prefilled, max(budget, 1)
                )
                gate = self._mm_gate(seq.req, pos_end)
                if gate == "wait":
                    # Park in `deferred` (re-fronted after the scan), NOT
                    # back into _waiting: the head-admission loop below
                    # treats any _Seq it sees as fresh/preempted — it
                    # would pop a second slot and overwrite the held
                    # block_ids (leaking both) if this seq reached it.
                    deferred.append(seq)
                    continue
                if gate != "ready":
                    # Expired/desynced stream: release the held slot +
                    # blocks (this seq is not in _running — nothing else
                    # can reclaim them) and error-finish.
                    self.block_mgr.free(seq.block_ids)
                    seq.block_ids = []
                    self._free_slots.append(seq.slot)
                    rejects.append(
                        (seq.req, StatusCode.UNAVAILABLE, gate)
                    )
                    continue
            # Mid-prefill re-match: blocks that landed since the last
            # chunk (a fabric peer fetch racing this prefill, a streamed
            # PD chunk, a sibling's commit) are adopted at the chunk
            # boundary — the remaining tail shrinks instead of
            # recomputing KV the cache now holds.
            self._extend_midchunk_match(seq)
            chunk = min(len(seq.tokens) - seq.prefilled, max(budget, 1))
            budget -= chunk
            seq.chunk_len = chunk
            if seq.head_hash is not None:
                pending_hashes.add(seq.head_hash)
            batch.append(seq)

        # Priority admission (hybrid online/offline): stable-partition the
        # queue so every online item precedes every offline one. Relative
        # order within each class is preserved; mid-chunk seqs were
        # already extracted above, so nothing here holds blocks.
        with self._lock:
            if any(self._item_req(x).offline for x in self._waiting) and any(
                not self._item_req(x).offline for x in self._waiting
            ):
                ordered = sorted(
                    self._waiting, key=lambda x: self._item_req(x).offline
                )  # sort is stable: online (False) first
                self._waiting.clear()
                self._waiting.extend(ordered)

        while budget > 0:
            with self._lock:
                if not self._waiting:
                    break
                head_item = self._waiting[0]
                head = self._item_req(head_item)
                # Sanity-reject BEFORE any preemption decision: evicting
                # offline work for a head that is then rejected would
                # sacrifice its KV for nothing (review finding, r4).
                htoks = (
                    head_item.tokens if isinstance(head_item, _Seq)
                    else head_item.prompt_token_ids
                )
                if len(htoks) >= self.cfg.max_seq_len:
                    self._waiting.popleft()
                    rejects.append(
                        (head, StatusCode.INVALID_ARGUMENT,
                         "prompt exceeds max_seq_len")
                    )
                    continue
                if math.ceil(
                    (len(htoks) + 1) / self.block_size
                ) > pool_capacity:
                    self._waiting.popleft()
                    rejects.append(
                        (head, StatusCode.RESOURCE_EXHAUSTED,
                         "request needs more KV blocks than the pool holds")
                    )
                    continue
                if head.mm_stream is not None:
                    # Streamed encoder handoff: admit only when the first
                    # chunk's placeholders have landed; otherwise defer
                    # WITHOUT blocking the queue behind this request.
                    gate = self._mm_gate(head, min(len(htoks), budget))
                    if gate == "wait":
                        self._waiting.popleft()
                        deferred.append(head_item)
                        continue
                    if gate != "ready":
                        self._waiting.popleft()
                        rejects.append(
                            (head, StatusCode.UNAVAILABLE, gate)
                        )
                        continue
                no_slot = not self._free_slots
            if no_slot:
                # Online head + every slot busy: preempt a running OFFLINE
                # decode (recompute-style) instead of stalling the burst.
                if not self._preempt_offline_for(head):
                    break
                continue
            with self._lock:
                if not self._waiting or not self._free_slots:
                    # only this thread pops the head, but re-check anyway
                    break
                item = self._waiting[0]
                tokens = item.tokens if isinstance(item, _Seq) else item.prompt_token_ids
                n_tok = len(tokens)
                if n_tok >= self.cfg.max_seq_len:
                    self._waiting.popleft()
                    rejects.append(
                        (self._item_req(item), StatusCode.INVALID_ARGUMENT,
                         "prompt exceeds max_seq_len")
                    )
                    continue
                # Need blocks for all current tokens + the next one.
                need_total = math.ceil((n_tok + 1) / self.block_size)
                if need_total > pool_capacity:
                    # Can NEVER fit — reject instead of stalling the queue
                    # head forever.
                    self._waiting.popleft()
                    rejects.append(
                        (self._item_req(item), StatusCode.RESOURCE_EXHAUSTED,
                         "request needs more KV blocks than the pool holds")
                    )
                    continue
                if not self.block_mgr.can_allocate(need_total):
                    blocked_on_pool = True
                else:
                    blocked_on_pool = False
                    self._waiting.popleft()
            if blocked_on_pool:
                # Online head + pool pressure: free blocks by preempting a
                # running OFFLINE decode, then retry this head.
                if not self._preempt_offline_for(self._item_req(item)):
                    break
                continue

            # Hash OUTSIDE the lock (long prompts hash thousands of blocks;
            # add_request/cancel must not stall behind it). Safe: this
            # thread is the only one that pops/appendlefts _waiting.
            # Media requests bypass the cache (their KV depends on encoder
            # embeddings the token-id hash cannot see); so do LoRA-adapter
            # requests — their KV depends on the adapter, and the chained
            # token-id hashes are adapter-blind (a base/other-adapter hit
            # would serve the WRONG cached KV).
            req0 = self._item_req(item)
            has_media = req0.has_media or bool(req0.adapter_idx)
            head_hashes = (
                []
                if has_media
                else prefix_block_hashes(
                    tokens[: n_tok - 1], self.block_size, self.block_mgr.seed
                )
            )
            if head_hashes and head_hashes[0] in pending_hashes:
                # Defer: shares a prefix with this batch — next step's
                # prefix match will reuse the blocks this batch commits.
                with self._lock:
                    self._waiting.appendleft(item)
                break

            if isinstance(item, _Seq):  # resuming a preempted sequence
                seq = item
                seq.slot = self._free_slots.pop()
                if self.state_family:
                    self.state_recomputes += 1
            else:
                seq = _Seq(item, self._free_slots.pop())
            # Prefix-cache match — never the entire context (at least one
            # token must run to produce logits). The hash chain (already
            # computed for the dedup check) is shared with the host-tier
            # continuation. Media requests bypass the cache entirely
            # (head_hashes is empty for them).
            hashes = head_hashes
            num_cached, cached_blocks = self.block_mgr.match_prefix(
                seq.tokens[: n_tok - 1], hashes=hashes
            )
            if self.host_pool is not None and not has_media:
                num_cached, cached_blocks = self._extend_match_from_host(
                    hashes, num_cached, list(cached_blocks)
                )
            seq.num_cached = num_cached
            seq.block_ids = list(cached_blocks)
            seq.window_lo = 0
            seq.last_committed_block = len(cached_blocks) - 1
            new_blocks = need_total - len(cached_blocks)
            try:
                seq.block_ids += self.block_mgr.allocate(new_blocks)
            except OutOfBlocksError:
                self.block_mgr.free(seq.block_ids)
                seq.block_ids = []
                self._free_slots.append(seq.slot)
                with self._lock:
                    self._waiting.appendleft(item)
                break
            if not isinstance(item, _Seq):
                # Prefix-cache effectiveness counters, AFTER allocation
                # succeeds — an OutOfBlocksError requeue retries the same
                # raw item and would double-count (review finding, r5);
                # preemption resumes (_Seq items) re-match their own
                # blocks and are not cache "hits". bench_serving reports
                # the fleet hit rate from these.
                self.prefix_cached_tokens += num_cached
                self.prefix_prompt_tokens += max(n_tok - 1, 0)

            # Chunked prefill: the step budget is STRICT — a long uncached
            # suffix prefills across steps (decode runs between chunks, so
            # one long prompt no longer spikes every running request's
            # TBT). The sequence keeps its slot and blocks while waiting
            # for its next chunk.
            seq.prefilled = seq.num_cached
            seq.chunk_len = min(len(seq.tokens) - seq.prefilled, budget)
            seq.head_hash = hashes[0] if hashes else None
            seq.admit_hashes = hashes  # mid-prefill re-match walks these
            budget -= seq.chunk_len
            pending_hashes.update(hashes)
            if mixed_collect is not None and self._mixed_eligible(seq):
                seq.pf_dispatched = seq.prefilled
                self._pf_active[seq.req.request_id] = seq
                # One Lpad bucket per mixed dispatch (byte-parity with
                # _prefill_group's same-bucket grouping): a seq whose
                # first chunk pads differently still ADMITS now (slot +
                # blocks held) but its chunk rides the next iteration's
                # dispatch via _continue_pf_chunks.
                if (
                    len(mixed_collect) < getattr(
                        self.executor, "PREFILL_GROUP_MAX", 8
                    )
                    and (
                        not mixed_collect
                        or self.executor.bucket_len(seq.chunk_len)
                        == self.executor.bucket_len(mixed_collect[0][2])
                    )
                ):
                    mixed_collect.append(
                        (seq, seq.prefilled, seq.chunk_len)
                    )
                continue
            batch.append(seq)

        if deferred:
            # Deferred streamed-media items return to the FRONT in their
            # original relative order (stream landings set the work event,
            # so the next step re-checks their coverage).
            with self._lock:
                self._waiting.extendleft(reversed(deferred))
        admitted = self._prefill_admitted(batch) if batch else 0
        for req, code, msg in rejects:
            self._reject(req, code, msg)
        return admitted

    def _mm_gate(self, req: EngineRequest, pos_end: int) -> str:
        """Streamed-media admission gate for one prefill chunk ending at
        absolute position `pos_end` (docs/EPD.md): "ready" when every
        placeholder below it has landed (materializing the final arrays
        once the stream completes), "wait" while chunks are in flight, or
        an error message when the stream desynced or hit its deadline
        (the caller error-finishes — exactly the legacy timeout surface,
        moved off the HTTP thread)."""
        ms = req.mm_stream
        if ms is None:
            return "ready"
        err = ms.failed()
        if err:
            return f"media embedding stream failed: {err}"
        if ms.complete():
            emb, pos = ms.assembled()
            req.mm_embeds = emb
            req.mm_positions = [int(p) for p in pos]
            req.mm_stream = None
            return "ready"
        if ms.expired():
            return "media embeddings never arrived (stream deadline)"
        return "ready" if ms.ready_upto(pos_end) else "wait"

    def _sp_eligible(self, s: _Seq) -> bool:
        """Whether this seq routes through the sequence-parallel ring
        prefill (prefill_long). The ring recomputes from position 0 (no
        prefix reuse), so SP is only a win when the prompt is long AND
        mostly uncached (uncached suffix >= 8x the cached prefix).
        Mid-chunk seqs stay batched (the ring would discard landed
        chunks); LoRA / min_p / logit_bias / guided / penalized-resume
        requests stay batched because prefill_long samples without those
        features. Shared by the split prefill router and the mixed-step
        eligibility check."""
        sp_thresh = self.cfg.sp_prefill_threshold
        if sp_thresh <= 0 or not getattr(self.executor, "supports_sp", False):
            return False
        sp = s.req.sampling
        penalized_resume = s.generated and (
            getattr(sp, "presence_penalty", 0.0)
            or getattr(sp, "frequency_penalty", 0.0)
        )
        return (
            not s.req.has_media
            and not s.req.adapter_idx
            and not getattr(sp, "min_p", 0.0)
            and not getattr(sp, "logit_bias", ())
            and not s.req.guided
            and not penalized_resume
            and s.prefilled <= s.num_cached
            and len(s.tokens) - s.num_cached >= sp_thresh
            and len(s.tokens) - s.num_cached >= 8 * s.num_cached
        )

    def _mixed_eligible(self, seq: _Seq) -> bool:
        """Whether a freshly admitted seq can ride the fused mixed step.
        Media prompts (embedding injection + M-RoPE streams), streamed
        encoder handoffs, and SP-ring prompts keep the split prefill
        path. Guided requests DO ride the mixed batch (ISSUE 13): their
        final chunk samples under a host-derived mask row applied
        in-graph (_build_pf_items), and their decode steps run
        host-paced inside the pipeline instead of forcing split.
        prefill_only requests (the PD prefill role, incl. kv_stream
        sessions) stay split: they never decode — there is nothing
        to fuse with — and their per-chunk KV exports are timed to the
        synchronous prefill loop (docs/PD_DISAGGREGATION.md)."""
        req = seq.req
        return (
            not req.has_media
            and req.mm_stream is None
            and not req.prefill_only
            and not self._sp_eligible(seq)
        )

    @thread_owned("engine")
    def _prefill_admitted(self, batch: List[_Seq]) -> int:
        """Split prefill (no fused step): build the items and launch them
        (`dispatch`; the executor's blocking read is `device_wait`), then
        book the results (`emit`)."""
        # Long-context path: prompts past the SP threshold prefill over the
        # mesh's sequence-parallel ring (ring attention) one at a time;
        # they skip prefix reuse (ring attends from position 0) and media
        # requests stay on the batched path (embedding injection).
        if self.cfg.sp_prefill_threshold > 0 and getattr(
            self.executor, "supports_sp", False
        ):
            sp_batch = [s for s in batch if self._sp_eligible(s)]
            if sp_batch:
                batch = [s for s in batch if s not in sp_batch]
                done = self._prefill_sp(sp_batch)
                return done + (
                    self._prefill_admitted(batch) if batch else 0
                )
        phase = self._phases.phase
        with phase("dispatch"):
            items = self._split_pf_items(batch)
            t0 = time.monotonic()
            for seq in batch:
                # First chunk: TTFT base. The unset check (0.0 = never
                # set) covers a seq whose first chunk never dispatched
                # before adoption advanced `prefilled` past num_cached
                # (mixed-mode requeue after a mode flip).
                if seq.prefilled <= seq.num_cached or (
                    seq.prefill_start_time == 0.0
                ):
                    self._first_dispatch(seq, t0)
            self._m_kernel_dispatch.labels(
                kernel=self._kernel_names["prefill"]
            ).inc(self._prefill_group_count(items))
            self.prefill_chunks += len(items)
            self.prefill_tokens += sum(len(it.token_ids) for it in items)
            # annotate=False: the executor's leaf annotations stay leaves
            with phase("dispatch", annotate=False):
                outs = self.executor.prefill_batch(items)
        with phase("emit"):
            return self._book_split_prefill(batch, items, outs)

    @thread_owned("engine")
    def _split_pf_items(self, batch: List[_Seq]) -> list:
        """PrefillItems for the split path's due chunks."""
        from xllm_service_tpu.runtime.executor import PrefillItem

        items = []
        for seq in batch:
            s = seq.req.sampling
            start = seq.prefilled
            n = seq.chunk_len or (len(seq.tokens) - start)
            table = self._table(seq, start, start + n)
            # Media embeddings for this chunk: final arrays, or — on a
            # still-streaming handoff — whatever items have landed (the
            # admission gate guaranteed in-chunk coverage; the executor
            # drops positions outside the chunk).
            mm_e = mm_p = None
            if seq.req.has_media:
                if seq.req.mm_stream is not None:
                    mm_e, mm_p = seq.req.mm_stream.assembled()
                else:
                    mm_e = np.asarray(seq.req.mm_embeds, np.float32)
                    mm_p = np.asarray(seq.req.mm_positions, np.int64)
            items.append(
                PrefillItem(
                    token_ids=np.asarray(
                        seq.tokens[start:start + n], np.int32
                    ),
                    start_pos=start,
                    block_table=table,
                    slot=seq.slot,
                    temperature=s.temperature,
                    top_k=s.top_k,
                    top_p=s.top_p,
                    seed=s.seed,
                    step=len(seq.generated),
                    mm_embeds=(
                        np.asarray(mm_e, np.float32)
                        if mm_e is not None else None
                    ),
                    mm_positions=(
                        np.asarray(mm_p, np.int64)
                        if mm_p is not None else None
                    ),
                    rope_positions=(
                        self._mrope_positions(seq)[:, start:start + n]
                        if self._mrope_active(seq)
                        else None
                    ),
                    presence=getattr(s, "presence_penalty", 0.0),
                    frequency=getattr(s, "frequency_penalty", 0.0),
                    # Only the FINAL chunk's sampled token survives, so
                    # intermediate chunks skip the bias (and its compiled
                    # variant), like prior_tokens below.
                    logit_bias=(
                        tuple(getattr(s, "logit_bias", ()) or ())
                        if start + n >= len(seq.tokens)
                        else ()
                    ),
                    mask_row=(
                        self._guided_row(seq)
                        if seq.req.guided
                        and self._guided_tokens is not None
                        and start + n >= len(seq.tokens)
                        else -1
                    ),
                    adapter_idx=seq.req.adapter_idx,
                    # final chunk only, like logit_bias/mask_row: the
                    # intermediate chunks' sampled tokens are discarded
                    min_p=(
                        getattr(s, "min_p", 0.0)
                        if start + n >= len(seq.tokens)
                        else 0.0
                    ),
                    # Only the FINAL chunk's sampled token survives, so
                    # intermediate chunks skip the [P, V] histogram (and
                    # the penalized compiled variant) entirely.
                    prior_tokens=(
                        np.asarray(
                            [t for t, _ in seq.generated], np.int32
                        )
                        if seq.generated
                        and start + n >= len(seq.tokens)
                        and (
                            getattr(s, "presence_penalty", 0.0)
                            or getattr(s, "frequency_penalty", 0.0)
                        )
                        else None
                    ),
                )
            )
        return items

    @thread_owned("engine")
    def _book_split_prefill(self, batch: List[_Seq], items, outs) -> int:
        now = time.monotonic()
        admitted = 0
        for seq, item, (tok, lp) in zip(batch, items, outs):
            end = seq.prefilled + len(item.token_ids)
            if end < len(seq.tokens):
                # Partial chunk: KV landed; the chunk-tail "token" sampled
                # from a mid-prompt position is discarded. The seq returns
                # to the queue (holding slot + blocks) for its next chunk;
                # decode steps run in between. Counts as progress (the
                # loop must not back off between chunks).
                seq.prefilled = end
                self._stream_chunk_kv(seq)
                with self._lock:
                    self._waiting.appendleft(seq)
                admitted += 1
                continue
            seq.prefilled = end
            # Client-perceived TTFT spans ALL chunks (+ interleaved decode
            # steps) from the first chunk's start — for single-chunk seqs
            # this is the whole batched step: slightly pessimistic per seq,
            # conservative for the TimePredictor fit.
            ms = (now - seq.prefill_start_time) * 1000
            self._finish_prefill(
                seq, tok, lp, now, ms,
                len(seq.tokens) - seq.num_cached,
            )
            admitted += 1
        return admitted

    @thread_owned("engine")
    def _finish_prefill(
        self,
        seq: "_Seq",
        tok: int,
        lp: float,
        now: float,
        ms: float,
        profiled_len: int,
    ) -> None:
        """Shared post-prefill bookkeeping for the batched and SP paths:
        TTFT windows + profiling curve, block commit, first token, running
        insert, emit, and the prefill-only handoff."""
        self._window_append(self._ttft_window, now, ms)
        self._m_ttft.observe(ms)
        if len(self._profile_ttft) < PROFILE_SAMPLES:
            self._profile_ttft.append((profiled_len, ms))
        seq.prefill_done_time = seq.last_token_time = now
        self._commit_full_blocks(seq)
        seq.generated.append((tok, lp))
        seq.tokens.append(tok)
        # Penalty state: (re)build this slot's generated-token histogram —
        # fresh admission carries one token, preemption/PD resume the full
        # history. Skipped for penalty-free requests (the common case):
        # their counts are never READ, and any later penalized occupant of
        # the slot re-seeds on its own admission — so the prefill hot path
        # avoids a scatter over the donated [R, V] histogram.
        s = seq.req.sampling
        if (
            getattr(s, "presence_penalty", 0.0)
            or getattr(s, "frequency_penalty", 0.0)
        ) and hasattr(self.executor, "seed_slot_counts"):
            self.executor.seed_slot_counts(
                seq.slot, [t for t, _ in seq.generated]
            )
        self._slot_admit(seq)
        self._running[seq.slot] = seq
        alive = self._emit(seq, finished=self._check_stop(seq))
        if alive and seq.req.prefill_only:
            self._handoff(seq)

    def _prefill_group_count(self, items) -> int:
        """How many compiled dispatches executor.prefill_batch will launch
        for these items — executor.prefill_groups IS its grouping walk —
        so the kernel-dispatch counter counts DEVICE dispatches, not
        engine-level calls. Fake executors without bucketing count as
        one."""
        groups = getattr(self.executor, "prefill_groups", None)
        if groups is None or not items:
            return 1
        return len(groups(items))

    @thread_owned("engine")
    def _prefill_sp(self, batch: List[_Seq]) -> int:
        """Ring-attention prefill for long prompts (one jitted call per
        sequence; the sp mesh ring IS the batch dimension here). The ring
        attends from position 0, so a prefix-cache match is traded for
        FRESH blocks — overwriting shared cached blocks with a recompute
        would mutate other sequences' context mid-flight."""
        admitted = 0
        for seq in batch:
            if seq.num_cached:
                self.block_mgr.free(seq.block_ids)
                need_total = math.ceil(
                    (len(seq.tokens) + 1) / self.block_size
                )
                try:
                    seq.block_ids = self.block_mgr.allocate(need_total)
                except OutOfBlocksError:
                    seq.block_ids = []
                    self._free_slots.append(seq.slot)
                    with self._lock:
                        self._waiting.appendleft(seq)
                    continue
                seq.num_cached = 0
                seq.last_committed_block = -1
            table = np.zeros((self.max_blocks,), np.int32)
            table[: len(seq.block_ids)] = seq.block_ids
            s = seq.req.sampling
            t0 = time.monotonic()
            self._first_dispatch(seq, t0)
            self._m_kernel_dispatch.labels(kernel="ring-sp").inc()
            self.prefill_chunks += 1
            self.prefill_tokens += len(seq.tokens)
            with self._phases.phase("dispatch", annotate=False):
                tok, lp = self.executor.prefill_long(
                    np.asarray(seq.tokens, np.int32),
                    table,
                    temperature=s.temperature,
                    top_k=s.top_k,
                    top_p=s.top_p,
                    seed=s.seed,
                    step=len(seq.generated),
                )
            now = time.monotonic()
            ms = (now - t0) * 1000
            with self._phases.phase("emit"):
                self._finish_prefill(seq, tok, lp, now, ms, len(seq.tokens))
            admitted += 1
        return admitted

    # ------------------------------------------------- host (DRAM) tier

    def _offload_to_host(self, items: List[Tuple[int, bytes]]) -> List[bytes]:
        """BlockManager eviction hook: copy ALL victims' KV to the host pool
        in one bulk device->host transfer BEFORE the device blocks are
        reused. Returns the hashes saved, which become offload('dram')
        heartbeat deltas instead of removed."""
        kv = np.asarray(
            self.executor.export_blocks([b for b, _ in items])
        )  # [2, L, n, Hkv, BS, D] — one device sync for the batch
        for i, (_, block_hash) in enumerate(items):
            for ev_hash, ev_kv in self.host_pool.put(block_hash, kv[:, :, i]):
                self._demote_to_ssd(ev_hash, ev_kv)
        # Only report hashes that SURVIVED the whole batch: a later put()
        # may have LRU-evicted an earlier one — claiming it saved would
        # leave a dangling DRAM entry in the master's index.
        return [h for _, h in items if h in self.host_pool]

    def _demote_to_ssd(self, block_hash: bytes, kv: np.ndarray) -> None:
        """DRAM eviction lands on disk when the SSD tier is enabled
        (dram->ssd transition, reference proto:47); otherwise the hash is
        gone from this instance — the fabric's coordinated-eviction hook
        gets one last look at the host array (offer the block to an
        under-utilized peer) before the local drop is recorded."""
        if self.ssd_pool is None:
            hook = self.on_cold_evict
            if hook is not None:
                try:
                    hook(block_hash, kv)
                except Exception:
                    logging.getLogger(__name__).exception(
                        "on_cold_evict hook failed; block drops locally"
                    )
            self.block_mgr.record_host_removed(block_hash)
            return
        for dropped in self.ssd_pool.put(block_hash, kv):
            self._record_cold_removed(dropped)
        self.block_mgr.record_tier_offload(block_hash, "ssd")

    def _record_cold_removed(self, block_hash: bytes) -> None:
        """A cold tier dropped this hash — but another tier may still hold
        it (DRAM re-population after an SSD spill); only report the tier
        the instance still serves from, never a false removal."""
        if self.host_pool is not None and block_hash in self.host_pool:
            self.block_mgr.record_tier_offload(block_hash, "dram")
        elif self.ssd_pool is not None and block_hash in self.ssd_pool:
            self.block_mgr.record_tier_offload(block_hash, "ssd")
        else:
            self.block_mgr.record_host_removed(block_hash)

    def _extend_match_from_host(
        self, hashes: List[bytes], num_cached: int, cached_blocks: List[int]
    ) -> Tuple[int, List[int]]:
        """Continue a prefix match into the host tier: consecutive host-held
        blocks after the HBM hit are re-imported (one bulk host->device copy)
        and recommitted, re-promoting their index entries to HBM."""
        start = len(cached_blocks)
        run: List[Tuple[bytes, np.ndarray]] = []
        for h in hashes[start:]:
            kv = self.host_pool.get(h)
            if kv is None and self.ssd_pool is not None:
                kv = self.ssd_pool.get(h)
            if kv is None:
                break
            run.append((h, kv))
        if not run or not self.block_mgr.can_allocate(len(run)):
            return num_cached, cached_blocks
        try:
            ids = self.block_mgr.allocate(len(run))
        except OutOfBlocksError:
            return num_cached, cached_blocks
        stacked = np.stack([kv for _, kv in run], axis=2)  # [2, L, n, ...]
        self.executor.import_blocks(stacked, np.asarray(ids))
        for bid, (h, _) in zip(ids, run):
            self.block_mgr.commit_block(bid, h)
        return num_cached + len(run) * self.block_size, cached_blocks + ids

    # ------------------------------------------------- prefix KV fabric

    @thread_owned("engine")
    def _extend_midchunk_match(self, seq: _Seq,
                               frontier: Optional[int] = None) -> int:
        """Chunk-boundary cache pickup: if the NEXT un-prefilled blocks'
        hashes are now committed locally (they landed after admission —
        a fabric peer fetch, a streamed PD chunk, a sibling sequence's
        commit), swap the sequence's fresh blocks for the cached ones and
        advance past them. This is what makes a peer fetch genuinely
        OVERLAP chunked prefill of the uncovered tail: each chunk
        boundary re-checks, so blocks that arrive mid-prefill are
        adopted instead of recomputed. Only runs on block-aligned
        boundaries; `last_committed_block` is left alone so the normal
        commit walk still registers this sequence's own chunks.

        `frontier=None` (the split prefill loop) adopts from and
        advances `seq.prefilled`. The mixed step builder instead passes
        its DISPATCHED frontier (`pf_dispatched`) so adoption stays live
        under the chunk pipeline: an in-flight chunk writes only blocks
        BELOW the frontier, every swapped block lies wholly beyond it,
        and `prefilled` catches up when the next chunk — cut from the
        advanced frontier — drains. Returns the tokens adopted (the
        caller's frontier advance)."""
        hashes = seq.admit_hashes
        bs = self.block_size
        start = seq.prefilled if frontier is None else frontier
        if (
            not hashes
            or start % bs
            or seq.req.has_media
            or seq.req.adapter_idx
        ):
            return 0
        idx = start // bs
        adopted = 0
        while idx < len(hashes) and idx < len(seq.block_ids):
            bid = self.block_mgr.lookup_hash(hashes[idx])
            if bid is None:
                break
            if bid == seq.block_ids[idx]:
                # Already swapped in by a mixed-frontier adoption
                # (frontier=pf_dispatched) before a mode flip requeued
                # this seq: `prefilled` never caught up, so count the
                # block covered NOW — cutting the next split chunk from
                # `prefilled` would recompute KV into a CACHED block
                # other live sequences hold references to.
                if frontier is None and idx * bs >= seq.prefilled:
                    seq.prefilled = (idx + 1) * bs
                    idx += 1
                    continue
                break
            # Swap: take a cache reference on the committed block, release
            # this seq's never-written fresh block back to the pool.
            old = seq.block_ids[idx]
            self.block_mgr.acquire_cached(bid)
            self.block_mgr.free([old])
            seq.block_ids[idx] = bid
            if frontier is None:
                seq.prefilled += bs
            adopted += 1
            idx += 1
        if adopted:
            self.prefix_cached_tokens += adopted * bs
            self.midprefill_adopted_blocks += adopted
        return adopted * bs

    def export_cached_blocks(
        self, hashes: List[bytes], timeout: float = 10.0
    ) -> Tuple[List[bytes], Optional[np.ndarray]]:
        """Serve a peer's prefix fetch: export the KV of every requested
        hash this instance holds on ANY tier. Thread-safe entry (HTTP
        serving thread); the export itself runs on the engine thread —
        the block manager and host/SSD pools are engine-thread-only, and
        an off-thread device export could read a block mid-eviction.
        Returns (served_hashes, kv [2, L, n, Hkv, BS, D]) with kv a HOST
        array, or ([], None) on timeout / nothing held."""
        job = {
            "hashes": [bytes(h) for h in hashes],
            "event": threading.Event(),
            "result": ([], None),
        }
        with self._lock:
            self._pending_exports.append(job)
        self._work.set()
        if not job["event"].wait(timeout):
            return [], None
        return job["result"]

    @thread_owned("engine")
    def _drain_export_requests(self) -> None:
        while True:
            with self._lock:
                if not self._pending_exports:
                    return
                job = self._pending_exports.popleft()
            try:
                job["result"] = self._export_cached(job["hashes"])
            except Exception:
                logging.getLogger(__name__).exception(
                    "prefix-fabric block export failed; peer recomputes"
                )
                job["result"] = ([], None)
            finally:
                job["event"].set()

    @thread_owned("engine")
    def _export_cached(self, hashes: List[bytes]):
        """Engine-thread export body: HBM blocks gather in ONE device
        export; host/SSD blocks read from their pools. Requested order is
        preserved in the stacked result. On a tp-sharded executor an
        all-HBM export stays PER-SHARD end-to-end (shard_wire.ShardedKV:
        each tp shard's host copy reads off its own device — no
        cross-shard gather; the /kv/fetch frame then ships N per-shard
        block sets). Mixing in host/SSD-tier blocks — stored flat —
        degrades that response to the flat layout."""
        from xllm_service_tpu.parallel import shard_wire

        served: List[bytes] = []
        seen: Set[bytes] = set()
        arrays: Dict[bytes, np.ndarray] = {}
        # Per-shard per-block pieces [nc, L, Hc/tp, BS, D] (head axis 2
        # once the block axis is sliced away) for sharded HBM exports.
        pieces: Dict[bytes, List[np.ndarray]] = {}
        hbm: List[Tuple[bytes, int]] = []
        for h in hashes:
            if h in seen:
                continue  # duplicate hash in the request
            seen.add(h)
            bid = self.block_mgr.lookup_hash(h)
            if bid is not None:
                hbm.append((h, bid))
                served.append(h)
                continue
            kv = self.host_pool.get(h) if self.host_pool is not None else None
            if kv is None and self.ssd_pool is not None:
                kv = self.ssd_pool.get(h)
            if kv is not None:
                arrays[h] = np.asarray(kv)
                served.append(h)
        if hbm:
            stacked = shard_wire.to_host(
                self.executor.export_blocks([b for _, b in hbm])
            )
            if isinstance(stacked, shard_wire.ShardedKV):
                for i, (h, _) in enumerate(hbm):
                    pieces[h] = [
                        np.asarray(s)[:, :, i] for s in stacked.shards
                    ]
            else:
                for i, (h, _) in enumerate(hbm):
                    arrays[h] = stacked[:, :, i]
        if not served:
            return [], None
        if pieces and not arrays:
            nsh = len(next(iter(pieces.values())))
            return served, shard_wire.ShardedKV([
                np.stack([pieces[h][s] for h in served], axis=2)
                for s in range(nsh)
            ])
        for h, pc in pieces.items():
            arrays[h] = np.concatenate(pc, axis=2)
        return served, np.stack([arrays[h] for h in served], axis=2)

    # ------------------------------------------------- PD disaggregation

    def _stream_chunk_kv(self, seq: _Seq) -> None:
        """Pipelined handoff: after a PARTIAL prefill chunk lands, export
        the newly completed full blocks to the request's kv_stream hook so
        they migrate while the next chunk is still prefilling. Safe vs.
        later prefill steps: export_blocks gathers into a fresh device
        buffer, and prompt blocks below `prefilled` are never rewritten.
        Media/LoRA prompts never stream (their KV never enters the
        hash-addressed migration path) and neither do resumed sequences
        (generated history makes the token/hash split ambiguous)."""
        req = seq.req
        stream = req.kv_stream
        if (
            stream is None
            or not req.prefill_only
            or getattr(stream, "aborted", False)
            or req.has_media
            or req.adapter_idx
            or seq.generated
        ):
            return
        avail = seq.prefilled // self.block_size
        if avail <= seq.streamed_blocks:
            return
        prompt_len = len(seq.tokens)
        hashes = self._stream_prefix_hashes(seq, avail)
        chunk = KVStreamChunk(
            request_id=req.request_id,
            start_block=seq.streamed_blocks,
            block_hashes=hashes[seq.streamed_blocks: avail],
            kv=self.executor.export_blocks(
                seq.block_ids[seq.streamed_blocks: avail]
            ),
            prompt_tokens=prompt_len,
            total_blocks_hint=prompt_len // self.block_size,
        )
        try:
            ok = stream.send_chunk(chunk)
        except Exception:  # hook errors must not kill the engine loop
            logging.getLogger(__name__).exception(
                "kv_stream hook failed for %s; falling back to the "
                "monolithic handoff", req.request_id,
            )
            ok = False
        if ok:
            seq.streamed_blocks = avail

    def _stream_prefix_hashes(self, seq: _Seq, nblocks: int) -> List[bytes]:
        """Chained hashes of seq.tokens' first `nblocks` full blocks,
        extended INCREMENTALLY across chunks via the per-seq cache —
        rehashing the whole prefix per chunk would be O(blocks x chunks)
        on exactly the long prompts the pipeline targets."""
        from xllm_service_tpu.common.hashing import extend_prefix_block_hashes

        cache = extend_prefix_block_hashes(
            seq.stream_hashes, seq.tokens, nblocks,
            self.block_size, self.block_mgr.seed,
        )
        return cache[:nblocks]

    @thread_owned("engine")
    def _handoff(self, seq: _Seq) -> None:
        """Prefill side: export this sequence's full committed blocks and
        hand them to the peer transport, then release the local sequence.
        The committed blocks stay in the local prefix cache (evictable), so
        cache-aware routing keeps its affinity signal."""
        full = seq.last_committed_block + 1
        if full <= 0:
            hashes = []
        elif seq.req.kv_stream is not None:
            # Streaming requests: extend the per-chunk hash cache instead
            # of rehashing the whole prefix a second time.
            hashes = self._stream_prefix_hashes(seq, full)
        else:
            hashes = prefix_block_hashes(
                seq.tokens[: full * self.block_size],
                self.block_size,
                self.block_mgr.seed,
            )
        # Pipelined handoff: blocks already delivered through the stream
        # session ride nothing twice — the commit payload carries only the
        # tail. A session that aborted (peer rejection / send failure)
        # falls back to the full monolithic export: the blocks are still
        # held right here, so the retry is free.
        streamed = seq.streamed_blocks
        stream = seq.req.kv_stream
        if stream is not None and getattr(stream, "aborted", False):
            streamed = 0
        streamed = max(0, min(streamed, full))
        kv = None
        if full > streamed:
            # Stays a DEVICE array: the in-process (colocated-PD / ICI
            # analog) path imports it without ever touching the host; the
            # HTTP/DCN path converts at serialization (kv_frame_to_bytes).
            # Safe vs. the block free below: export_blocks gathers into a
            # fresh buffer on the device stream before any later step can
            # rewrite the freed blocks.
            kv = self.executor.export_blocks(seq.block_ids[streamed:full])
        payload = KVHandoff(
            request_id=seq.req.request_id,
            token_ids=list(seq.tokens),
            first_token=seq.generated[0][0],
            first_logprob=seq.generated[0][1],
            num_full_blocks=full,
            block_hashes=list(hashes),
            kv=kv,
            usage_prompt_tokens=len(seq.req.prompt_token_ids),
            kv_start_block=streamed,
        )
        try:
            seq.req.handoff(payload)
        except Exception:
            import traceback

            traceback.print_exc()
            # The commit will never be sent — don't leak the session.
            self._dispose_stream(seq.req)
        # release slot + block refs; committed blocks become evictable-cached
        if seq.slot in self._running:
            del self._running[seq.slot]
            self._free_slots.append(seq.slot)
            self._slot_clear(seq.slot)
        self.block_mgr.free(seq.block_ids)
        seq.block_ids = []

    def import_sequence(
        self, req: EngineRequest, handoff: KVHandoff
    ) -> None:
        """Decode side: continue a sequence prefilled by a peer. Thread-safe
        entry; the KV landing happens on the engine thread."""
        self._no_state_handoff()
        with self._lock:
            self._pending_imports.append((req, handoff))
        self._work.set()

    def import_kv_blocks(self, block_hashes: List[bytes], kv) -> None:
        """Pipelined-handoff receive side: land one streamed chunk's full
        blocks into the local prefix cache (committed under their chained
        hashes, immediately evictable). Thread-safe entry; the landing runs
        on the engine thread. The later commit handoff's admission picks
        the blocks up through the ordinary prefix match — a chunk that
        never arrives only costs recompute of its span."""
        self._no_state_handoff()
        with self._lock:
            self._pending_kv_chunks.append((list(block_hashes), kv))
        self._work.set()

    def _drain_imports(self) -> None:
        while True:
            with self._lock:
                if not self._pending_kv_chunks:
                    break
                hashes, kv = self._pending_kv_chunks.popleft()
            try:
                self._land_migrated_blocks(hashes, kv)
            except Exception:
                # Counted (xllm_engine_kv_chunk_land_errors_total): the
                # chunk was already acked to the sender, so a landing
                # failure is otherwise invisible until the commit's
                # prefix match silently recomputes.
                self.kv_chunk_land_errors += 1
                logging.getLogger(__name__).exception(
                    "streamed KV chunk failed to land; the commit will "
                    "recompute its span"
                )
        while True:
            with self._lock:
                if not self._pending_imports:
                    return
                req, h = self._pending_imports.popleft()
            self._do_import(req, h)

    def _land_migrated_blocks(self, hashes: List[bytes], kv) -> None:
        """Land migrated full blocks into the local cache under their
        chained hashes (hashes[i] names kv[:, :, i]); blocks whose hash is
        already cached locally are skipped (dedup). Shared by the
        monolithic handoff import and the streamed-chunk path. Raises on
        malformed payloads — callers degrade to recompute."""
        expect = self.executor.migration_shape(len(hashes))
        if kv.shape != expect:
            raise ValueError(
                f"handoff KV shape {kv.shape} != local cache layout "
                f"{expect} — PD pair config mismatch; recomputing"
            )
        if any(
            not isinstance(hb, bytes) or len(hb) != 16 for hb in hashes
        ):
            raise ValueError("malformed block hash in handoff; recomputing")
        fresh = [
            i
            for i, hb in enumerate(hashes)
            if self.block_mgr.lookup_hash(hb) is None
        ]
        ids = []
        if fresh:
            try:
                ids = self.block_mgr.allocate(len(fresh))
            except OutOfBlocksError:
                ids = []
        if ids:
            try:
                self.executor.import_blocks(
                    kv[:, :, np.asarray(fresh, np.int32)],
                    np.asarray(ids),
                )
            except Exception:
                self.block_mgr.free(ids)
                raise
            for bid, i in zip(ids, fresh):
                self.block_mgr.commit_block(bid, hashes[i])
            # drop our temporary ref; blocks stay evictable-cached
            # until admission re-acquires them via match_prefix
            self.block_mgr.free(ids)

    def _do_import(self, req: EngineRequest, h: KVHandoff) -> None:
        # Land migrated full blocks into the local cache under their chained
        # hashes. On ANY problem — capacity, a PD pair whose engine configs
        # diverge (block_size/layers/heads/dtype), a corrupt payload — fall
        # back to pure recompute: the resume _Seq below is seeded regardless,
        # so admission prefills the whole prompt locally and the request
        # never vanishes. A pipelined handoff's kv covers only blocks
        # [kv_start_block, num_full_blocks) — the earlier ones arrived (or
        # were lost, costing only recompute) through the streamed chunks.
        start = max(int(getattr(h, "kv_start_block", 0) or 0), 0)
        if h.num_full_blocks > start and h.kv is not None:
            try:
                if len(h.block_hashes) != h.num_full_blocks:
                    raise ValueError(
                        f"{len(h.block_hashes)} block hashes for "
                        f"{h.num_full_blocks} blocks; recomputing"
                    )
                # numpy from the HTTP/DCN path; a device jax.Array from the
                # in-process local path (no host round-trip — the slice and
                # import below run device-side).
                self._land_migrated_blocks(h.block_hashes[start:], h.kv)
            except Exception:
                import traceback

                traceback.print_exc()
        # Seed a resume-sequence: prompt + first generated token; admission
        # treats it like a preempted sequence — prefix match picks up the
        # imported blocks, only the sub-block tail is recomputed, and the
        # next emitted token is the SECOND one (the prefill peer already
        # streamed the first).
        seq = _Seq(req, slot=-1)
        seq.tokens = list(h.token_ids)
        seq.generated = [(h.first_token, h.first_logprob)]
        with self._lock:
            self._waiting.append(seq)
        self._work.set()

    @staticmethod
    def _dispose_stream(req: EngineRequest) -> None:
        """A request that will never hand off tears its streaming session
        down (peer-side entry + offer keepalives) instead of leaking it
        until the receiver's TTL reap."""
        stream = req.kv_stream
        if stream is None:
            return
        try:
            fn = getattr(stream, "dispose", None)
            if fn is not None:
                fn()
        except Exception:
            pass

    def _reject(self, req: EngineRequest, code: StatusCode, msg: str) -> None:
        self._dispose_stream(req)
        out = RequestOutput(
            request_id=req.request_id,
            status=Status(code, msg),
            finished=True,
        )
        self._unhanded = True
        try:
            req.callback(out)
        except Exception:
            pass

    def _notify_cancelled(self, req: EngineRequest) -> None:
        self._dispose_stream(req)
        out = RequestOutput(
            request_id=req.request_id,
            finished=True,
            cancelled=True,
            status=Status(StatusCode.CANCELLED, "cancelled"),
        )
        self._unhanded = True
        try:
            req.callback(out)
        except Exception:
            pass

    # -------------------------------------------------------------- decode

    @thread_owned("engine")
    def _ensure_decode_capacity(self, width: int, mask=None) -> None:
        """Ensure block capacity for every position the coming decode step
        may write: `width` tokens starting at each slot's next input
        position (the persistent dispatch position — one token ahead of
        seq.tokens while a step is in flight), capped at max_seq_len.
        Preempts (victim-first, then self) on pool exhaustion. `mask`
        restricts the pass to dispatchable slots (overlap mode skips
        length-stopped slots whose position already sits at the limit)."""
        max_len = self.cfg.max_seq_len
        for slot, seq in sorted(self._running.items()):
            if slot not in self._running:  # preempted earlier this pass
                continue
            if mask is not None and not mask[slot]:
                continue
            pos = int(self._ps_positions[slot])
            tl = max(1, min(width, max_len - pos))
            need = (pos + tl - 1) // self.block_size + 1
            while len(seq.block_ids) < need:
                try:
                    seq.block_ids += self.block_mgr.allocate(1)
                    self._block_tables[slot, len(seq.block_ids) - 1] = (
                        seq.block_ids[-1]
                    )
                except OutOfBlocksError:
                    victim = self._pick_preemption_victim(exclude=slot)
                    if victim is None:
                        # Nothing to preempt: preempt this seq itself.
                        self._preempt(seq)
                        break
                    self._preempt(victim)
            else:
                if self.window_family:
                    self._slide_window(
                        seq, pos, pos + tl,
                        self._block_tables[slot, self.max_blocks:],
                    )
                continue

    # ------------------------------------------- persistent batch state

    def _set_opt(self, arr: np.ndarray, slot: int, val, count_attr: str):
        """Write one optional-feature array entry, maintaining the count of
        nonzero entries so _sampling_batch_view can pass None (and keep the
        cheaper compiled variant) when the feature is unused batch-wide."""
        old = arr[slot]
        arr[slot] = val
        setattr(
            self, count_attr,
            getattr(self, count_attr) + int(bool(val)) - int(bool(old)),
        )

    @thread_owned("engine")
    def _slot_admit(self, seq: _Seq) -> None:
        """Install a sequence's sampling params + dispatch state into the
        persistent per-slot arrays (fresh admission, preemption resume, PD
        import resume). Together with _slot_clear this is the ONLY write
        path for sampling state — steady-state decode steps reuse the
        arrays untouched instead of rebuilding a SamplingBatch."""
        slot = seq.slot
        s = seq.req.sampling
        self._ps_temps[slot] = s.temperature
        self._ps_top_k[slot] = s.top_k
        self._ps_top_p[slot] = s.top_p
        self._ps_seeds[slot] = s.seed & 0xFFFFFFFF
        self._ps_steps[slot] = len(seq.generated)
        self._ps_presence[slot] = getattr(s, "presence_penalty", 0.0)
        self._ps_frequency[slot] = getattr(s, "frequency_penalty", 0.0)
        self._set_opt(
            self._ps_min_p, slot, getattr(s, "min_p", 0.0), "_n_min_p"
        )
        self._set_opt(
            self._ps_adapter, slot, seq.req.adapter_idx, "_n_adapter"
        )
        self._set_opt(
            self._ps_rope_delta, slot, getattr(seq, "rope_delta", 0) or 0,
            "_n_rope",
        )
        bias = tuple(getattr(s, "logit_bias", ()) or ())
        self._n_bias += int(bool(bias)) - int(bool(self._bias_rows[slot]))
        self._bias_rows[slot] = bias
        if seq.req.guided:
            self._guided_slots.add(slot)
        else:
            self._guided_slots.discard(slot)
        row = self._block_tables[slot]
        row[:] = 0
        row[: len(seq.block_ids)] = seq.block_ids
        self._ps_active[slot] = True
        self._ps_last_tok[slot] = seq.tokens[-1]
        self._ps_positions[slot] = len(seq.tokens) - 1
        self._ps_pending[slot] = 0
        self._ps_gen_count[slot] = len(seq.generated)
        self._ps_tok_count[slot] = len(seq.tokens)
        self._ps_max_new[slot] = s.max_new_tokens
        self._fresh[slot] = True
        seq.admit_gen += 1
        self._ps_gen += 1

    @thread_owned("engine")
    def _slot_clear(self, slot: int) -> None:
        """Reset one slot's persistent arrays (finish/cancel/preempt/
        handoff) — inactive rows carry the same neutral values the old
        per-step rebuild zero-filled them with."""
        self._ps_active[slot] = False
        self._ps_pending[slot] = 0
        self._ps_temps[slot] = 0.0
        self._ps_top_k[slot] = 0
        self._ps_top_p[slot] = 1.0
        self._ps_seeds[slot] = 0
        self._ps_steps[slot] = 0
        self._ps_presence[slot] = 0.0
        self._ps_frequency[slot] = 0.0
        self._set_opt(self._ps_min_p, slot, 0.0, "_n_min_p")
        self._set_opt(self._ps_adapter, slot, 0, "_n_adapter")
        self._set_opt(self._ps_rope_delta, slot, 0, "_n_rope")
        self._n_bias -= int(bool(self._bias_rows[slot]))
        self._bias_rows[slot] = ()
        self._guided_slots.discard(slot)
        self._block_tables[slot, :] = 0
        self._ps_last_tok[slot] = 0
        self._ps_positions[slot] = 0
        self._ps_gen_count[slot] = 0
        self._ps_tok_count[slot] = 0
        self._ps_max_new[slot] = 0
        self._fresh[slot] = False
        self._ps_gen += 1

    def _refresh_slot_arrays(self, slot: int, seq: _Seq) -> None:
        """Re-derive a slot's dispatch state from host truth. The
        speculative path emits a VARIABLE token count per step, so the
        incremental +1 advances the plain paths use would drift."""
        self._ps_steps[slot] = len(seq.generated)
        self._ps_positions[slot] = len(seq.tokens) - 1
        self._ps_last_tok[slot] = seq.tokens[-1]
        self._ps_gen_count[slot] = len(seq.generated)
        self._ps_tok_count[slot] = len(seq.tokens)

    def _sampling_batch_view(self) -> SamplingBatch:
        """SamplingBatch over the persistent arrays — zero per-step
        allocation. The packed logit-bias arrays are cached keyed on the
        running-set generation (_ps_gen), so the no-bias common case never
        calls pack_logit_bias and steady-state biased batches pack once per
        membership change, not once per step."""
        if self._n_bias:
            if self._bias_cache_gen != self._ps_gen:
                from xllm_service_tpu.ops.sampling import pack_logit_bias

                self._bias_cache = pack_logit_bias(self._bias_rows, self.R)
                self._bias_cache_gen = self._ps_gen
            bias_ids, bias_vals = self._bias_cache
        else:
            bias_ids = bias_vals = None
        return SamplingBatch(
            self._ps_temps, self._ps_top_k, self._ps_top_p, self._ps_seeds,
            self._ps_steps, self._ps_presence, self._ps_frequency,
            bias_ids, bias_vals,
            adapter_idx=self._ps_adapter if self._n_adapter else None,
            min_p=self._ps_min_p if self._n_min_p else None,
            rope_delta=self._ps_rope_delta if self._n_rope else None,
        )

    def _observe_host_gap(self) -> None:
        """Record the host-bookkeeping gap between the previous step's
        drain and this dispatch — the window depth 0 spends with the
        device idle, and depth 1 hides behind the in-flight step."""
        if self._t_host_free is not None:
            gap = (time.monotonic() - self._t_host_free) * 1000
            self._m_host_gap.observe(gap)
            self.host_gap_ms_sum += gap
            self.host_gap_steps += 1

    # ------------------------------------------------------------- drain

    @thread_owned("engine")
    def _drain_step(
        self, flt: Optional[_InFlight], newer: Optional[_InFlight]
    ) -> int:
        """Consume one in-flight step's results (blocks until the device
        finishes it — while `newer`, if any, already executes behind it;
        at depth 0 `flt` is the step just dispatched and `newer` is
        None). Per-token emit, tracer windows, block commits, and stop
        checks all live here, off the dispatch path. Late tokens for
        sequences no longer running are discarded; surviving slots not
        covered by a newer dispatch return to host feeding."""
        if flt is None:
            return 0
        n_emit = None
        with self._phases.phase("device_wait"):
            tokens = np.asarray(flt.tokens)
            logprobs = np.asarray(flt.logprobs)
            if flt.n_emit is not None:
                n_emit = np.asarray(flt.n_emit)
            if flt.moe:
                for pairs in self.executor.book_moe(flt.moe).tolist():
                    self._m_moe_pairs.observe(pairs)
        with self._phases.phase("emit"):
            return self._book_step(flt, newer, tokens, logprobs, n_emit)

    @thread_owned("engine")
    def _book_step(self, flt: _InFlight, newer: Optional[_InFlight],
                   tokens: np.ndarray, logprobs: np.ndarray,
                   n_emit: Optional[np.ndarray]) -> int:
        """_drain_step's host half, once the results are on the host. A
        plain decode step emits one token a surviving slot (`tokens` [R]
        or, mixed, [R + P]); a verify step (`n_emit` given, `tokens`
        [R, S]) its accepted prefix + the corrected/bonus token, 1..S."""
        step_ms = (time.monotonic() - flt.t0) * 1000
        self._profile_step(flt.nactive, flt.total_ctx, step_ms)
        spec = n_emit is not None
        toks, lps = tokens.tolist(), logprobs.tolist()
        produced = 0
        tbts: List[float] = []
        now = time.monotonic()
        for slot, (seq, gen) in flt.slots.items():
            if self._running.get(slot) is not seq or seq.admit_gen != gen:
                # The seq stopped/cancelled/was preempted after dispatch
                # (admit_gen also catches a preempt + re-admission of the
                # SAME seq into the SAME slot): one-step-late stop — the
                # slot's WHOLE row of over-produced samples is dropped (a
                # preempted seq re-samples it deterministically on
                # resume; same (seed, step) key, same context).
                self.late_stop_discards += 1
                continue
            self._ps_pending[slot] -= 1
            if spec:
                n = int(n_emit[slot])
                self._m_spec_accepted.observe(n)
                self.spec_tokens_emitted += n
                row = zip(toks[slot][:n], lps[slot][:n])
            else:
                n = 1
                row = ((toks[slot], lps[slot]),)
            if n:
                tbts.append((now - seq.last_token_time) * 1000)
                seq.last_token_time = now
            produced += self._book_row(slot, seq, gen, newer, row, spec)
        if tbts:
            self._m_tbt.observe_many(tbts)
            self._window_append(self._tbt_window, now, max(tbts))
        produced += self._drain_pf_rows(flt, tokens, logprobs)
        if self.span_hook is not None and produced:
            # One span per drained STEP BATCH (never per token): the
            # engine's decode cadence on the merged timeline.
            self.span_hook(
                "", "step_batch",
                nactive=flt.nactive, produced=produced,
                step_ms=round(step_ms, 3),
            )
        # The step's last callback has run: its outputs leave together.
        self._step_boundary()
        self._t_host_free = time.monotonic()
        return produced

    @thread_owned("engine")
    def _book_row(self, slot: int, seq: _Seq, gen: int,
                  newer: Optional[_InFlight], row, spec: bool) -> int:
        """THE booking of one surviving slot's emitted (token, logprob)
        pairs (one for a plain decode step): history appends, block
        commits, the stop check and the callback per token — a finish
        or a cancel drops the rest of the row — then the slot's host
        dispatch state. A slot that a newer dispatch does not cover
        returns to host feeding. Returns the tokens emitted."""
        emitted = 0
        for tok, lp in row:
            seq.generated.append((tok, lp))
            seq.tokens.append(tok)
            self._commit_full_blocks(seq)
            emitted += 1
            if not self._emit(seq, finished=self._check_stop(seq)):
                return emitted  # finished or cancelled: slot cleared
        if spec:
            # Variable emission: positions and step counts re-derive
            # from token truth (the device adds the in-flight step's
            # accepted count itself); a plain step's advanced by one at
            # dispatch and stand.
            self._refresh_slot_arrays(slot, seq)
        else:
            self._ps_last_tok[slot] = tok
            self._ps_gen_count[slot] += 1
            self._ps_tok_count[slot] += 1
        ent = newer.slots.get(slot) if newer is not None else None
        if ent is None or ent[0] is not seq or ent[1] != gen:
            self._fresh[slot] = True
        return emitted

    @thread_owned("engine")
    def _drain_pf_rows(self, flt: _InFlight, tokens, logprobs) -> int:
        """Prefill rows riding a fused dispatch: advance `prefilled`,
        keep the PD chunk stream fed, and on the FINAL chunk run the
        shared post-prefill bookkeeping (_finish_prefill installs the
        slot — the seq starts decoding host-fed next dispatch). A seq
        whose entry no longer matches _pf_active was cancelled after
        dispatch: its chunk's sampled token is discarded like any
        late-stop token. admit_gen guards the same _Seq object being
        re-admitted between dispatch and drain, like the decode-slot
        check. Plain mixed steps carry the pf samples at output rows
        [R + j]; speculative verify steps carry them in pf_tok/pf_lp."""
        pf_tok = pf_lp = None
        if flt.pf_tok is not None:
            with self._phases.phase("device_wait"):
                pf_tok = np.asarray(flt.pf_tok)
                pf_lp = np.asarray(flt.pf_lp)
        produced = 0
        for seq, gen, j, c_start, c_end, queued_ms in flt.pf:
            if (
                self._pf_active.get(seq.req.request_id) is not seq
                or seq.admit_gen != gen
            ):
                self.late_stop_discards += 1
                continue
            seq.prefilled = c_end
            if self.span_hook is not None:
                # Per prefill CHUNK (bounded by chunk count, not tokens);
                # keyed by the engine request id — the instance layer's
                # srid-keyed admit span brackets the whole prefill.
                # A request's first chunk carries its engine queue wait.
                extra = (
                    {} if queued_ms is None
                    else {"queued_ms": round(queued_ms, 3)}
                )
                self.span_hook(
                    seq.req.request_id, "prefill_chunk",
                    prefilled=c_end, total=len(seq.tokens),
                    final=c_end >= len(seq.tokens), **extra,
                )
            if c_end < len(seq.tokens):
                self._stream_chunk_kv(seq)
                produced += 1
                continue
            del self._pf_active[seq.req.request_id]
            if pf_tok is not None:
                tok = int(pf_tok[j])
                lp = float(pf_lp[j])
            else:
                tok = int(tokens[self.R + j])
                lp = float(logprobs[self.R + j])
            fin = time.monotonic()
            ms = (fin - seq.prefill_start_time) * 1000
            self._finish_prefill(
                seq, tok, lp, fin, ms, len(seq.tokens) - seq.num_cached
            )
            produced += 1
        return produced

    # ------------------------------------------------------------ M-RoPE

    def _mrope_active(self, seq: _Seq) -> bool:
        return bool(
            getattr(self.executor.cfg, "mrope_section", ())
            and seq.req.has_media
        )

    def _mrope_positions(self, seq: _Seq) -> np.ndarray:
        """[3, len(seq.tokens)] (t, h, w) rope streams for a media
        sequence — the HF Qwen2-VL get_rope_index algorithm for square
        still-image grids: text advances all three streams together; an
        image span of m = g*g merged tokens pins t at the span start,
        lays h/w on the g x g grid, and resumes text at start + g. Also
        fixes the sequence's rope_delta (generation positions continue
        from the compressed maximum, not the token count).

        Covers GENERATED tokens too — preemption/PD resume re-prefills
        prompt + generated, so the streams extend on demand with the
        compressed continuation (token i: i + rope_delta, all equal)."""
        need = len(seq.tokens)
        if seq.rope_pos3 is not None and seq.rope_pos3.shape[1] >= need:
            return seq.rope_pos3
        if seq.rope_pos3 is not None:
            base = seq.rope_pos3
            have = base.shape[1]
            ext = (
                np.arange(have, need, dtype=np.int32) + seq.rope_delta
            )[None, :].repeat(3, axis=0)
            seq.rope_pos3 = np.concatenate([base, ext], axis=1)
            return seq.rope_pos3
        L = len(seq.req.prompt_token_ids)
        pos = np.zeros((3, L), np.int32)
        spans = []  # (start, length) contiguous placeholder runs
        mm = sorted(int(p) for p in seq.req.mm_positions)
        run_start = None
        prev = None
        for p in mm:
            if run_start is None:
                run_start = prev = p
                continue
            if p == prev + 1:
                prev = p
                continue
            spans.append((run_start, prev - run_start + 1))
            run_start = prev = p
        if run_start is not None:
            spans.append((run_start, prev - run_start + 1))
        grids = [tuple(int(v) for v in g) for g in (seq.req.mm_grids or ())]
        gi = 0  # next undeclared-grid index (document order, like spans)
        cur = 0  # next rope position value
        idx = 0  # next prompt index to fill
        for s0, m in spans:
            while idx < s0:  # text before the span
                pos[:, idx] = cur
                cur += 1
                idx += 1
            # Declared grids (HF get_rope_index, video-capable): consume
            # greedily — ADJACENT media parts share one contiguous
            # placeholder run, so a span may cover several grids. Each
            # grid's t stream advances per temporal slice of gh*gw
            # tokens, h/w lay the slice; text (or the next medium)
            # resumes at cur + max(t, gh, gw).
            rem = m
            while rem > 0 and gi < len(grids):
                t, gh, gw = grids[gi]
                n_g = t * gh * gw
                if n_g > rem:
                    break
                sl = gh * gw
                for j in range(n_g):
                    pos[0, idx + j] = cur + j // sl
                    pos[1, idx + j] = cur + (j % sl) // gw
                    pos[2, idx + j] = cur + j % gw
                cur += max(t, gh, gw)
                idx += n_g
                rem -= n_g
                gi += 1
            if rem == 0:
                continue
            m = rem
            g = int(round(math.sqrt(m)))
            if g * g != m:
                # non-square span (unknown grid): degrade to sequential
                for j in range(m):
                    pos[:, idx + j] = cur + j
                cur += m
            else:
                for j in range(m):
                    pos[0, idx + j] = cur
                    pos[1, idx + j] = cur + j // g
                    pos[2, idx + j] = cur + j % g
                cur += g
            idx += m
        while idx < L:
            pos[:, idx] = cur
            cur += 1
            idx += 1
        seq.rope_pos3 = pos
        seq.rope_delta = cur - L  # <= 0: image spans compress positions
        if need > L:  # resumed with generated history: extend now
            return self._mrope_positions(seq)
        return pos

    # --------------------------------------------------- guided decoding

    def set_lora_adapters(self, adapters) -> "Dict[str, int]":
        """Install LoRA adapters on the executor (see
        ModelExecutor.set_lora_adapters); returns {name: row}."""
        self.lora_names = self.executor.set_lora_adapters(adapters)
        return self.lora_names

    def set_guided_context(
        self, table: np.ndarray, token_bytes: List[bytes],
        eos_ids: Optional[List[int]] = None,
    ) -> None:
        """Install the JSON-mode mask table ([M, V] bool, one row per
        abstract automaton state — guided/json_fsm.token_mask_table) and
        the per-id byte surfaces the host tracker walks. `eos_ids` is the
        EOS set the TABLE was built with (engine EOS unioned with the
        tokenizer's — instance_serving._build_guided_context); schema
        bitmaps must use the same set or completed documents could never
        emit EOS in deployments where the engine's own set is empty."""
        self.executor.set_guided_table(table)
        self._guided_tokens = token_bytes
        self._guided_row_any = table.any(axis=1)
        self._guided_eos = (
            sorted(set(eos_ids)) if eos_ids is not None
            else sorted(self.eos_token_ids)
        )

    def _guided_row(self, seq: _Seq) -> int:
        """Mask-table row for the seq's NEXT sampled token, advancing the
        exact automaton through any not-yet-consumed emitted tokens.
        Returns the permissive row for unguided seqs, on automaton reject
        (cannot happen under the mask), or for an all-false row (vocab
        cannot express the needed byte — degrade open rather than hang)."""
        from xllm_service_tpu.guided import json_fsm

        perm = self.executor.permissive_row
        if self._guided_tokens is None:
            return perm
        if seq.req.guided == "json_schema":
            spec = seq.schema_spec
            if spec is None:  # first touch (False = compile failed, sticky)
                spec = self._schema_spec_for(seq.req)
                seq.schema_spec = spec if spec is not None else False
            if not spec:
                return perm
            st = self._advance_exact(seq, spec)
            if st is None:
                return perm
            return self._schema_state_row(spec, st)
        if seq.req.guided != "json":
            return perm
        st = self._advance_exact(seq, None)
        if st is None:
            return perm
        row = json_fsm.abstract_index(st)
        if self._guided_row_any is not None and not self._guided_row_any[row]:
            return perm
        return row

    def _advance_exact(self, seq: _Seq, spec):
        """Advance the seq's exact automaton (generic JSON when spec is
        None, schema otherwise) through unconsumed emitted tokens."""
        from xllm_service_tpu.guided import json_fsm, schema_fsm

        if seq.json_state == "INIT":
            seq.json_state = (
                schema_fsm.initial_state(spec) if spec is not None
                else json_fsm.initial_state()
            )
            seq.json_upto = 0
        st = seq.json_state
        toks = self._guided_tokens
        while st is not None and seq.json_upto < len(seq.generated):
            tok = seq.generated[seq.json_upto][0]
            tb = toks[tok] if 0 <= tok < len(toks) else b""
            st = (
                schema_fsm.advance_bytes(spec, st, tb) if spec is not None
                else json_fsm.advance_bytes(st, tb)
            )
            seq.json_upto += 1
        seq.json_state = st
        return st

    def _schema_spec_for(self, req: EngineRequest):
        """Compiled SchemaSpec for the request's schema (memoized by
        canonical schema JSON; compile errors were already rejected at
        the API layer — degrade open if one slips through)."""
        from xllm_service_tpu.guided import schema_fsm

        if req.schema is None:
            return None
        # NO sort_keys: declaration order IS the emission contract.
        key = json.dumps(req.schema, separators=(",", ":"))
        spec = self._schema_specs.get(key)
        if spec is None:
            try:
                spec = schema_fsm.compile_schema(req.schema)
            except schema_fsm.SchemaError:
                logging.getLogger(__name__).warning(
                    "json_schema compile failed post-admission; serving "
                    "unconstrained"
                )
                return None
            # Bounded memo: distinct schemas can be unbounded on a
            # long-lived server (per-request enum values etc.) — evict
            # oldest-inserted past the cap; live seqs keep their spec via
            # seq.schema_spec, so eviction only costs a recompile. The
            # row cache is swept of perm-degrade entries likewise (row
            # entries are already bounded by the dynamic region + flush).
            if len(self._schema_specs) >= 128:
                self._schema_specs.pop(next(iter(self._schema_specs)))
            if len(self._schema_row_cache) >= 8192:
                # perm-degrade entries accumulate without consuming rows;
                # recycle at the next step boundary (mid-step clears could
                # overwrite a row another slot was just assigned).
                self._schema_flush_pending = True
            self._schema_specs[key] = spec
        return spec

    def _schema_state_row(self, spec, st) -> int:
        """Dynamic-row index for an exact schema state: memoized (incl.
        permissive-degrade outcomes — recomputing a full-vocab bitmap per
        step would stall the batch); first visit computes the token
        bitmap and writes it into the executor table's dynamic region.
        On exhaustion the region is flushed BETWEEN steps (a mid-step
        flush could overwrite a row another slot was just assigned) and
        this state degrades open for one step."""
        from xllm_service_tpu.guided import schema_fsm

        ex = self.executor
        perm = ex.permissive_row
        base = getattr(ex, "dynamic_row_base", None)
        if base is None:
            return perm
        key = (spec.source_key, st)
        row = self._schema_row_cache.get(key)
        if row is not None:
            return row
        if self._schema_row_next >= getattr(ex, "num_dynamic_rows", 0):
            # Flush at the next step boundary; this step degrades open.
            if not self._schema_flush_pending:
                self._schema_flush_pending = True
                logging.getLogger(__name__).warning(
                    "guided json_schema: dynamic mask rows exhausted; "
                    "flushing the region at the next step"
                )
            return perm
        bits = self._schema_bitmap_cache.get(key)
        if bits is None:
            bits = self._compute_schema_bitmap(spec, st)
            self._schema_bitmap_put(key, bits)
        if not bits.any():
            self._schema_row_cache[key] = perm  # memoize the degrade
            return perm
        row = base + self._schema_row_next
        self._schema_row_next += 1
        ex.update_guided_row(row, bits)
        self._schema_row_cache[key] = row
        return row

    def _compute_schema_bitmap(self, spec, st) -> np.ndarray:
        """token_bitmap for one exact state (callable from ANY thread —
        everything it reads is immutable or benignly-racy)."""
        from xllm_service_tpu.guided import schema_fsm

        if self._schema_fbi is None:
            # Benign race: two threads may both build; either result is
            # correct and the GIL makes the attribute swap atomic.
            self._schema_fbi = schema_fsm.build_first_byte_index(
                self._guided_tokens
            )
        eos = getattr(self, "_guided_eos", None)
        return schema_fsm.token_bitmap(
            spec, st, self._schema_fbi, len(self._guided_tokens),
            eos if eos is not None else sorted(self.eos_token_ids),
        )

    def _schema_bitmap_put(self, key, bits: np.ndarray) -> None:
        cache = self._schema_bitmap_cache
        if len(cache) >= 4096:  # ~vocab/8 bytes per entry; bound memory
            try:
                cache.pop(next(iter(cache)))
            except (StopIteration, KeyError, RuntimeError):
                pass
        cache[key] = bits

    # Canonical-walk byte preferences: quote first (opens a string value
    # / closes string content), then brace-open, then terminators (end a
    # number / container, move to the next key), digits last so numbers
    # stay one digit — the walk emits one minimal document, visiting
    # every skeleton state and each value node's free-content entry
    # state once.
    _PREWARM_BYTES = (0x22, 0x7B, 0x7D, 0x5D, 0x2C, 0x3A, 0x31)

    def prewarm_schema(self, schema) -> None:
        """Called from the API layer at ADMISSION (HTTP thread) after the
        schema compiles: walk one canonical document through the
        automaton, computing and caching the token bitmap of every state
        visited — object skeleton, key strings, and each value's
        free-content state (the expensive ones: a free string accepts
        most of the vocab, ~vocab Python byte walks). By the time the
        engine step loop first assembles this request, the bitmaps it
        needs are cache hits, so running decodes never stall behind the
        byte walk (advisor finding, round 4). States off the canonical
        path (deep inside free content) still compute lazily on the
        loop, but those are the cheap self-loop variants."""
        from xllm_service_tpu.guided import schema_fsm

        if self._guided_tokens is None or schema is None:
            return
        try:
            spec = schema_fsm.compile_schema(schema)
        except schema_fsm.SchemaError:
            return
        # Once per distinct schema: repeat admissions of a warmed schema
        # skip the canonical walk entirely (review finding, r5). Set ops
        # are GIL-atomic; a racing double-walk is benign (same results).
        if spec.source_key in self._prewarmed_schema_keys:
            return
        if len(self._prewarmed_schema_keys) >= 512:
            self._prewarmed_schema_keys.clear()
        self._prewarmed_schema_keys.add(spec.source_key)
        st = schema_fsm.initial_state(spec)
        seen = set()
        for _ in range(512):  # walk bound (counters make states unique)
            if st is None or st in seen:
                return
            seen.add(st)
            key = (spec.source_key, st)
            if key not in self._schema_bitmap_cache:
                self._schema_bitmap_put(
                    key, self._compute_schema_bitmap(spec, st)
                )
            if schema_fsm.is_complete(st):
                return
            # Prefer a successor not yet visited (a whitespace or digit
            # self-loop must not end the walk while unvisited skeleton
            # remains); an all-seen frontier terminates via the cycle
            # check above.
            nxt = None
            fallback = None
            for b in (*self._PREWARM_BYTES, *range(256)):
                cand = schema_fsm.advance_byte_top(spec, st, b)
                if cand is None:
                    continue
                if cand not in seen:
                    nxt = cand
                    break
                if fallback is None:
                    fallback = cand
            st = nxt if nxt is not None else fallback

    def _maybe_flush_schema_rows(self) -> None:
        """Between-steps recycle of the dynamic mask-row region: drop the
        memo and restart allocation. Live sequences re-derive their rows
        from their current exact state on the next assembly, so no row
        index can be stale."""
        if self._schema_flush_pending:
            self._schema_flush_pending = False
            # Discard writes still buffered for pre-flush rows: the memo
            # clear makes every live row re-derive and re-stage, and a
            # stale buffered write must not share one batched
            # .at[rows].set with a fresh write to the same recycled index
            # (duplicate-index winner is unspecified in JAX — advisor
            # finding, round 4).
            pend = getattr(self.executor, "_pending_guided_rows", None)
            if pend is not None:
                pend.clear()
            self._schema_row_cache.clear()
            self._schema_row_next = 0

    def _guided_rows_spec(self, seq: _Seq, drafts: np.ndarray, S: int):
        """Per-position mask rows for a verify step: position 0 uses the
        current state; position j continues through drafts 0..j-1 (the
        accepted tokens ARE the drafts). An illegal draft leaves later
        positions permissive — sampling rejects at the illegal position
        anyway."""
        from xllm_service_tpu.guided import json_fsm, schema_fsm

        perm = self.executor.permissive_row
        rows = np.full((S,), perm, np.int32)
        r0 = self._guided_row(seq)
        rows[0] = r0
        if r0 == perm:
            return rows
        schema = seq.req.guided == "json_schema"
        # _guided_row above already resolved + cached the spec on the seq.
        spec = seq.schema_spec or None if schema else None
        st = seq.json_state
        toks = self._guided_tokens
        for j in range(1, S):
            d = int(drafts[j - 1])
            tb = toks[d] if 0 <= d < len(toks) else b""
            st = (
                schema_fsm.advance_bytes(spec, st, tb) if schema
                else json_fsm.advance_bytes(st, tb)
            )
            if st is None:
                break
            if schema:
                rows[j] = self._schema_state_row(spec, st)
            else:
                row = json_fsm.abstract_index(st)
                rows[j] = row if self._guided_row_any[row] else perm
        return rows

    # ------------------------------------------------- speculative decode

    def _propose_drafts(self, seq: _Seq, k: int) -> np.ndarray:
        """Prompt-lookup drafting: match the newest suffix n-gram (longest
        first, down to 1) against the sequence's own prompt+generation
        history and propose the k tokens that followed the most recent
        earlier occurrence. No draft model, no extra device work —
        repetitive text (code, quotes, structured output) accepts several
        tokens per step; random text degrades to plain decoding (the
        verify step always emits >= 1 token).

        O(ngram_max) per step (ISSUE 13 satellite): a per-seq rolling
        index maps each n-gram to the position AFTER its latest
        occurrence, extended incrementally as history grows — the old
        implementation re-materialized the lookback window and ran a
        sliding-window scan over every n-gram length on every step
        (O(lookback x ngram_max)). Gram-ends are indexed only up to
        len(tokens) - 2 (the newest gram has no follow token yet), so
        the suffix can never match itself; a long RESUMED history
        (preemption / PD import) back-fills in one pass bounded by
        `speculative_lookback`. Stale follow positions from a replaced
        token list (test stand-ins) fall through to shorter grams.
        Memory stays bounded by the lookback too: past ~2x the window's
        worth of entries the index rebuilds from the trailing window
        (amortized O(ngram_max)/step — the rebuild happens once per
        lookback's worth of emitted tokens)."""
        toks = seq.tokens
        m = len(toks)
        n_cfg = self.cfg.speculative_ngram_max
        lookback = self.cfg.speculative_lookback
        try:
            idx = seq.spec_ngrams
            upto = seq.spec_idx_upto
        except AttributeError:  # stand-in seq objects without the slots
            idx = seq.spec_ngrams = {}
            upto = 0
        if len(idx) > 2 * n_cfg * lookback:
            idx.clear()
            upto = 0
        start = max(upto, m - 1 - lookback)
        for end in range(start, m - 1):
            hi = end + 1
            for n in range(1, min(n_cfg, hi) + 1):
                idx[tuple(toks[hi - n: hi])] = hi
        seq.spec_idx_upto = max(m - 1, upto)
        n_max = min(n_cfg, m - 1)
        for n in range(n_max, 0, -1):
            f = idx.get(tuple(toks[m - n: m]))
            if f is not None:
                follow = toks[f: f + k]
                if follow:
                    out = np.empty((k,), np.int32)
                    out[: len(follow)] = follow
                    out[len(follow):] = follow[-1]
                    return out
        return np.full((k,), toks[-1], np.int32)

    @thread_owned("engine")
    def _dispatch_verify(
        self, items_meta: List[tuple]
    ) -> Optional[_InFlight]:
        """Dispatch the next speculative verify step without fetching
        results (executor.verify_start), fused with the due prefill
        chunks when `items_meta` holds any (the composed path: verify
        rows are q_len = k+1 prefill-shaped rows next to the chunks —
        docs/KERNELS.md). The step's verify inputs — last accepted
        token, position and step base — are gathered ON DEVICE from the
        in-flight step's output, so the VARIABLE accepted count never
        round-trips the host; the host proposes drafts from its
        one-step-late history (current history at depth 0), which is
        sound because point-mass acceptance makes the emitted stream
        draft-independent (ops/sampling.py). Guided slots join
        host-paced (exact automaton state at dispatch — their drafts
        AND mask rows derive from fully drained history); length-stops
        surface one step late as discards, and with a step in flight
        the capacity pass covers TWO steps of worst-case emission,
        because that step may advance a slot by up to S before this
        dispatch's writes land."""
        k = self.cfg.speculative_tokens
        S = k + 1
        R = self.R
        can = self._apply_guided_pacing(self._ps_active.copy())
        prev = self._inflight
        if can.any():
            self._ensure_decode_capacity(
                S if prev is None else 2 * S, mask=can
            )
            can &= self._ps_active  # the capacity pass may have preempted
        if not can.any() and not items_meta:
            return None
        batch = self._sampling_batch_view()
        fresh_mask = self._fresh | ~can
        assert prev is not None or bool(fresh_mask[can].all())
        drafts = np.zeros((R, k), np.int32)
        for slot in np.nonzero(can)[0]:
            drafts[int(slot)] = self._propose_drafts(
                self._running[int(slot)], k
            )
        if self._guided_tokens is not None and any(
            can[s] for s in self._guided_slots
        ):
            rows = np.full(
                (R, S), self.executor.permissive_row, np.int32
            )
            for slot in self._guided_slots:
                if can[slot]:
                    rows[slot] = self._guided_rows_spec(
                        self._running[slot], drafts[slot], S
                    )
            batch.mask_rows = rows
            self.guided_ingraph_steps += 1
        self._observe_host_gap()
        t0 = time.monotonic()
        items, pf_entries = self._build_pf_items(items_meta, t0)
        # annotate=False: the executor's leaf annotations stay leaves
        with self._phases.phase("dispatch", annotate=False):
            tokens, logprobs, n_emit, pf_tok, pf_lp = (
                self.executor.verify_start(
                    items,
                    drafts,
                    self._ps_last_tok,
                    self._ps_positions,
                    self._ps_steps,
                    fresh_mask,
                    prev.tokens if prev is not None else None,
                    prev.n_emit if prev is not None else None,
                    self._block_tables,
                    can,
                    batch,
                )
            )
        snapshot, nactive, total_ctx = self._snapshot_dispatch(
            can, len(items), "mixed" if items else "mq"
        )
        self.spec_steps += 1
        self.spec_slot_steps += nactive
        # a verify step drained at depth 0 is a sync step
        if self._force_sync:
            self.spec_sync_steps += 1
        else:
            self.spec_pipeline_steps += 1
        return _InFlight(
            tokens, logprobs, snapshot, t0, nactive, total_ctx,
            pf=pf_entries, n_emit=n_emit, pf_tok=pf_tok, pf_lp=pf_lp,
            moe=self._take_moe(),
        )

    # ---------------------------------------------------------- preemption

    def _pick_preemption_victim(self, exclude: int) -> Optional[_Seq]:
        candidates = [s for sl, s in self._running.items() if sl != exclude]
        if not candidates:
            return None
        # Offline work is always sacrificed before online work; within a
        # class, youngest first (least work lost on recompute).
        offline = [s for s in candidates if s.req.offline]
        pool = offline or candidates
        return max(pool, key=lambda s: s.req.arrival_time)

    @thread_owned("engine")
    def _preempt_offline_for(self, head: EngineRequest) -> bool:
        """Hybrid-scheduling preemption: an ONLINE head waiting on slots
        or blocks evicts one RUNNING offline decode (recompute-style; the
        victim requeues BEHIND online work and resumes when pressure
        clears). Returns False when the head is itself offline or no
        offline victim is running. Called WITHOUT self._lock held."""
        if head.offline:
            return False
        victims = [s for s in self._running.values() if s.req.offline]
        if not victims:
            return False
        victim = max(victims, key=lambda s: s.req.arrival_time)
        self._preempt(victim, requeue_front=False)
        return True

    @thread_owned("engine")
    def _preempt(self, seq: _Seq, requeue_front: bool = True) -> None:
        """Recompute-style preemption: release blocks and requeue the _Seq
        itself, preserving token history and generation accounting (KV is
        recomputed on re-admission; prefix-cache blocks soften the cost).
        Offline victims of online pressure requeue at the BACK
        (requeue_front=False) so the admission partition keeps online
        work ahead of them."""
        self.preemptions += 1
        self.block_mgr.free(seq.block_ids)
        seq.block_ids = []
        seq.last_committed_block = -1
        del self._running[seq.slot]
        self._free_slots.append(seq.slot)
        self._slot_clear(seq.slot)
        with self._lock:
            if requeue_front:
                self._waiting.appendleft(seq)
            else:
                self._waiting.append(seq)

    # ------------------------------------------------------------- commits

    def _commit_full_blocks(self, seq: _Seq) -> None:
        """Commit newly filled blocks under their chained hashes. Media
        requests never commit (their KV depends on encoder embeddings the
        token-id hash cannot see) and neither do LoRA-adapter requests
        (adapter-dependent KV under adapter-blind hashes); a state-pool
        family has nothing addressable by block hash."""
        if self.state_family or seq.req.has_media or seq.req.adapter_idx:
            return
        full = len(seq.tokens) // self.block_size
        committed = seq.last_committed_block + 1
        if full <= committed:
            return
        # one block from its parent's hash, not the prefix over again
        hashes = self._stream_prefix_hashes(seq, full)
        for i in range(committed, full):
            self.block_mgr.commit_block(seq.block_ids[i], hashes[i])
        seq.last_committed_block = full - 1

    # ---------------------------------------------------------------- stop

    def _check_stop(self, seq: _Seq) -> Optional[FinishReason]:
        s = seq.req.sampling
        tok = seq.tokens[-1]
        if not s.ignore_eos and tok in self.eos_token_ids:
            return FinishReason.STOP
        if tok in s.stop_token_ids:
            return FinishReason.STOP
        if len(seq.generated) >= s.max_new_tokens:
            return FinishReason.LENGTH
        if len(seq.tokens) >= self.cfg.max_seq_len:
            return FinishReason.LENGTH
        return None

    # ---------------------------------------------------------------- emit

    @thread_owned("engine")
    def _emit(self, seq: _Seq, finished: Optional[FinishReason]) -> bool:
        tok, lp = seq.generated[-1]
        s = seq.req.sampling
        seq_out = SequenceOutput(
            index=0,
            token_ids=[tok],
            finish_reason=finished or FinishReason.NONE,
        )
        if s.logprobs:
            seq_out.logprobs = [LogProb(data=LogProbData(token_id=tok, logprob=lp))]
        out = RequestOutput(
            request_id=seq.req.request_id,
            outputs=[seq_out],
            usage=Usage(
                num_prompt_tokens=len(seq.req.prompt_token_ids),
                num_generated_tokens=len(seq.generated),
            ),
            finished=finished is not None,
        )
        keep_going = True
        self._unhanded = True
        try:
            keep_going = seq.req.callback(out)
        except Exception:  # callback errors must not kill the engine loop
            import traceback

            traceback.print_exc()
            keep_going = False
        if finished is not None:
            self._finish(seq, finished)
            return False
        if keep_going is False:
            self._finish(seq, FinishReason.NONE, cancelled=True)
            return False
        return True

    @thread_owned("engine")
    def _finish(
        self, seq: _Seq, reason: FinishReason, cancelled: bool = False
    ) -> None:
        # A prefill_only request reaching _finish (cancel, or EOS/limit on
        # its very first token) will never run its handoff — its streaming
        # session must not leak on the decode peer.
        self._dispose_stream(seq.req)
        if seq.slot in self._running:
            del self._running[seq.slot]
            self._free_slots.append(seq.slot)
            self._slot_clear(seq.slot)
        self.block_mgr.free(seq.block_ids)
        seq.block_ids = []
        # Slot + blocks freed: wake a loop that backed off with waiting
        # work blocked on KV capacity (the event replaces the old blind
        # sleep in _loop).
        self._work.set()
        if cancelled:
            out = RequestOutput(
                request_id=seq.req.request_id,
                finished=True,
                cancelled=True,
                status=Status(StatusCode.CANCELLED, "cancelled"),
            )
            self._unhanded = True
            try:
                seq.req.callback(out)
            except Exception:
                pass
