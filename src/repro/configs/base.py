"""Model / runtime configuration dataclasses.

Every assigned architecture is expressed as a ``ModelConfig``. The Blink
serving engine treats the model as opaque (paper §4.3): all it needs is the
cache spec and the three step functions (train / prefill / decode) that
``repro.models.api`` derives from this config.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: str  # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int          # 0 for attention-free archs (rwkv)
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0       # 0 -> d_model // num_heads

    # --- attention options -------------------------------------------------
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    sliding_window: Optional[int] = None     # SWA width (mixtral, gemma2 local)
    local_global: bool = False               # gemma2: alternate local/global
    attn_softcap: Optional[float] = None     # gemma2: 50.0
    logit_softcap: Optional[float] = None    # gemma2: 30.0
    norm_type: str = "rmsnorm"               # rmsnorm | nonparametric_ln (olmo)
    qk_norm: bool = False                    # qwen3: per-head RMSNorm of q, k
    tie_embeddings: bool = False
    mlp_act: str = "silu"                    # silu | gelu

    # --- MoE ----------------------------------------------------------------
    num_experts: int = 0                     # the router's width
    top_k: int = 0
    moe_d_ff: int = 0                        # per-expert hidden dim
    shared_expert_d_ff: int = 0              # qwen2-moe shared experts (merged)
    # expert parallelism: this chip holds experts
    # [first_expert, first_expert + experts_held) of every layer; 0 = all
    experts_held: int = 0
    first_expert: int = 0

    # --- SSM ----------------------------------------------------------------
    ssm_state: int = 0                       # mamba2 N / rwkv head size driver
    ssm_conv: int = 4                        # mamba conv kernel width
    ssm_expand: int = 2                      # d_inner = expand * d_model
    ssm_head_dim: int = 64
    attn_every: int = 0                      # zamba2: shared attn every k layers

    # --- encoder-decoder ----------------------------------------------------
    is_encoder_decoder: bool = False
    num_encoder_layers: int = 0

    # --- multimodal stub ----------------------------------------------------
    modality: str = "text"                   # text | vision | audio
    num_modal_tokens: int = 0                # patch/frame embedding prefix len

    # --- numerics ------------------------------------------------------------
    dtype: str = "bfloat16"

    # ------------------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        if self.num_heads:
            return self.d_model // self.num_heads
        return 0

    @property
    def held_experts(self) -> int:
        """Experts whose weights this chip holds (all, unless a share)."""
        return self.experts_held or self.num_experts

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim if self.ssm_head_dim else 0

    @property
    def num_attn_layers(self) -> int:
        """How many layers carry a KV cache (paged attention)."""
        if self.arch_type == "ssm":
            return 0
        if self.arch_type == "hybrid":
            if not self.attn_every:
                return 0
            return (self.num_layers + self.attn_every - 1) // self.attn_every
        return self.num_layers

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def uses_paged_kv(self) -> bool:
        return self.num_attn_layers > 0

    @property
    def is_attention_free(self) -> bool:
        return self.arch_type == "ssm"

    @property
    def supports_long_context(self) -> bool:
        """True if decode cost is bounded independent of total context
        (SSM state, or sliding-window attention) -> eligible for long_500k."""
        if self.arch_type in ("ssm", "hybrid"):
            return True
        return self.sliding_window is not None

    def layer_window(self, layer: int) -> Optional[int]:
        """Effective attention window of layer `layer` (None = full)."""
        if self.local_global:
            return self.sliding_window if layer % 2 == 0 else None
        return self.sliding_window

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class InputShape:
    """One assigned (seq_len, global_batch) workload."""
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


TRAIN_4K = InputShape("train_4k", 4_096, 256, "train")
PREFILL_32K = InputShape("prefill_32k", 32_768, 32, "prefill")
DECODE_32K = InputShape("decode_32k", 32_768, 128, "decode")
LONG_500K = InputShape("long_500k", 524_288, 1, "decode")

INPUT_SHAPES = {s.name: s for s in (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)}


@dataclass(frozen=True)
class ServeConfig:
    """Blink engine runtime knobs (paper §4.2)."""
    num_slots: int = 64                 # ring buffer slots (paper: 4096)
    max_prompt_len: int = 256           # input arena per slot
    max_new_tokens: int = 64            # output arena per slot
    decode_batch: int = 8               # persistent decode batch width
    window: int = 120                   # fire-and-forget window (paper: 120)
    admit_per_step: int = 4             # prefill admissions per pause
    page_size: int = 16                 # KV page tokens
    num_pages: int = 512                # KV pool pages
    temperature: float = 0.0            # 0 => greedy
    top_p: float = 1.0
    eos_token: int = 2
    # attention backend for both serving phases: "gather" (jnp reference —
    # decode HBM traffic scales with max_kv, prefill materialises the T x T
    # logits) or "pallas" (paged-attention decode kernel + flash prefill
    # kernel). Env var REPRO_ATTN_BACKEND overrides. See
    # repro.models.attn_backend.
    attn_backend: str = "gather"
    # pallas: KV pages per DMA buffer slot of the attention kernels' page
    # loops; None = the split decode kernel fills 256 key rows per slot
    # (ops.paged_attention), the unified kernel takes 1
    attn_pages_per_block: Optional[int] = None
    kv_cache_dtype: Optional[str] = None  # e.g. "int8" (None = model dtype)
    # flash-prefill tile sizes (ROADMAP follow-up): forwarded to
    # make_model(prefill_block_q=..., prefill_block_k=...) by callers and
    # validated there (attn_backend.get_prefill_backend) at model-build time.
    prefill_block_q: int = 128
    prefill_block_k: int = 128
    # device-resident prefix KV cache (radix prefix reuse). When enabled the
    # frontend matches prompts against a DPU-plane radix trie
    # (frontend.prefix_index), shared prefix pages are refcounted in the
    # PageAllocator, admission allocates suffix pages only, and page release
    # moves from the decode branch to the frontend's slot-release path (the
    # trie must index freshly prefilled pages before they can be freed).
    prefix_cache: bool = False
    # evict LRU zero-external-ref trie chains when the free-page count drops
    # below this watermark. Independently of the watermark, both engines
    # always evict enough for the largest ring-pending admission (the
    # starvation fallback) — 0 means evict ONLY in that starving case.
    prefix_evict_watermark: int = 0
    # mixed-phase continuous batching (paper §4.2 pause-free variant):
    # 0 = phase-exclusive legacy scheduler (a step runs prefill OR decode);
    # > 0 = every engine step decodes ALL generating lanes AND advances at
    # most this many prompt tokens of pending prefill (chunk cursor carried
    # in ring.prefill_done_len through the PREFILLING lifecycle state), so
    # admission never head-of-line-blocks token emission. Requires a
    # paged-KV decoder-only arch (chunk resume rides the same cached_lens
    # machinery as radix prefix reuse). Greedy token streams are identical
    # under both policies — chunked prefill is bitwise-equal to single shot.
    prefill_chunk_tokens: int = 0
    # unify the mixed step's TWO attention dispatches (batched chunk
    # prefill + paged decode) into ONE ragged kernel call per iteration
    # (``kernels.ragged_attention``): decode lanes ride as q_len=1 rows of
    # the chunk bucket, prefill chunks as ragged rows, and the kernel's
    # epilogue merges the new tokens' K/V into their pool pages (int8:
    # quantised in-kernel — no float staging tensor). Greedy token streams
    # are identical to the split-dispatch path. Requires the mixed-phase
    # scheduler. See docs/ARCHITECTURE.md "Unified attention kernel".
    attn_unified: bool = False
    # opt-in interleaved K/V page layout ([P, KV, 2, ps, hd] — K and V of
    # a page share one buffer row), halving the page copies the unified
    # kernel issues per prefix block. Requires attn_unified; incompatible
    # with slo_preempt (the host offload path reads split pools).
    kv_fused_layout: bool = False
    # how many PREFILLING slots advance one chunk per step (bounds the
    # per-step prefill compute riding alongside decode; FCFS beyond it).
    # All of them share ONE prefill dispatch per iteration — the engine's
    # batched chunk step gathers every selected lane (heterogeneous chunk
    # cursors, ragged chunk lengths, per-lane cached prefixes) into a
    # single ``api.prefill_batched`` call.
    max_prefills_per_step: int = 1
    # adaptive chunk sizing (SLO-aware load shaping): 0 = static chunks of
    # exactly ``prefill_chunk_tokens``. > 0 = each mixed-step iteration
    # picks its per-lane chunk budget in
    # [prefill_block_q, prefill_chunk_tokens_max] from the decode-lane
    # occupancy snapshot (``engine.adaptive_chunk_budget``): near-full
    # decode batches shrink chunks toward the kernel tile floor so decode
    # iterations stay bounded; idle batches grow them toward the ceiling so
    # long prompts reach their first token sooner. The policy is a pure
    # integer function of ring state, mirrored bit-for-bit by the host
    # engine — the differential harness replays it on both planes. The
    # chunk bucket compiles at this ceiling (``chunk_bucket``).
    prefill_chunk_tokens_max: int = 0
    # --- SLO-aware overload control (paper Table 6/7 robustness story) ----
    # Number of SLO classes. Class 0 is the highest-priority (interactive)
    # class; higher indices are progressively more best-effort (batch).
    # With slo_classes == 1 every request is the same class and the
    # overload machinery degrades to plain FCFS.
    slo_classes: int = 1
    # Per-class TTFT target in ENGINE STEPS (len == slo_classes, required
    # when deadline_policy != "none"). Entry i is the budget, from
    # submission, for class i to emit its first token.
    slo_ttft_steps: Tuple[int, ...] = ()
    # Per-class steps-per-output-token budget (len == slo_classes, required
    # when deadline_policy == "e2e"). A request's end-to-end deadline is
    # ttft + tpot * max_new steps after submission.
    slo_tpot_steps: Tuple[int, ...] = ()
    # Deadline policy: "none" (no deadlines — requests never time out),
    # "ttft" (a request still waiting for its first token past its TTFT
    # deadline is CANCELLED; once streaming it is immune), or "e2e"
    # (requests are cancelled whenever the e2e deadline passes, including
    # mid-decode and while offloaded). Requires the mixed-phase scheduler:
    # the phase-exclusive engine has no per-step policy point.
    deadline_policy: str = "none"
    # Decode-lane preemption: when the earliest-deadline pending request
    # cannot admit for lack of pages or lanes, evict the worst-slack
    # strictly-lower-class DECODE_PROCESSING victim — its lane frees
    # immediately and its live KV spills to a host-side buffer at the next
    # window boundary (core.offload), to be restored byte-exact when
    # capacity allows. Requires mixed-phase and slo_classes >= 2 (there
    # must exist a class to sacrifice). Valid without deadlines: classes
    # alone drive victim choice.
    slo_preempt: bool = False
    # Bound on the DPU intake queue: enqueue beyond this many waiting
    # requests is REJECTED at submission (status "rejected", no tokens).
    # 0 = unbounded.
    intake_queue_limit: int = 0
    # Byte cap on the radix prefix trie's retained KV pages (prefix_cache
    # only). When the trie's pages exceed this many bytes of K/V pool
    # memory, zero-external-ref LRU chains are evicted PROACTIVELY at
    # every commit — not only under admission backpressure. 0 = unbounded
    # (watermark/starvation eviction still applies).
    prefix_trie_max_bytes: int = 0
    # --- fault plane (ring integrity, watchdog, crash recovery) -----------
    # Verify the per-entry payload checksum during the intake validation
    # sub-phase (ring_buffer.validate_intake): an entry whose stored
    # checksum does not match the recomputed one (a torn or bit-flipped
    # RDMA write) is quarantined in the terminal FAULTED state instead of
    # being admitted. Sequence/commit-flag/payload-range validation always
    # runs; this knob only disables the checksum compare (for rings whose
    # transport already provides end-to-end integrity).
    ring_checksum: bool = True
    # Fault any slot that makes no observable progress (chunk cursor,
    # token emission, or lifecycle transition) for this many consecutive
    # engine steps — a wedged PREFILLING lane, a decode lane streaming
    # nothing, or a torn PREFILL_PENDING entry whose commit flag never
    # arrives. 0 = watchdog off. States that legitimately wait (validated
    # PREFILL_PENDING under admission backpressure, DECODE_PAUSED,
    # PREEMPTED, OFFLOADED) are exempt. Requires the mixed-phase
    # scheduler; set it comfortably above the worst-case chunk-starvation
    # span (num_slots / max_prefills_per_step steps).
    watchdog_steps: int = 0
    # Snapshot the full engine state (ring, allocator, KV pages, RNG fold
    # state — core.recovery.snapshot_engine) every this many engine steps,
    # taken at window boundaries by the DPU plane. Restoring the snapshot
    # after a mid-stream window kill resumes greedy token streams
    # bit-for-bit (every policy is a pure function of engine state).
    # 0 = no snapshots. Must be a multiple of ``window`` (snapshots only
    # exist at window boundaries).
    snapshot_every_steps: int = 0
    # --- telemetry plane (CPU-free observability) -------------------------
    # The per-slot event log: EngineState's TelemetryState carries a
    # bounded (event code, step) timeline per ring slot, updated with pure
    # jnp diffs by every step and drained at window boundaries
    # (src/repro/telemetry/state.py). The per-step counter rows are
    # always on. Off = the log compiles out; on = identical Pallas
    # dispatch count, zero host callbacks, bitwise-identical streams.
    telemetry: bool = False
    # Bound on each slot's event log (event code + step stamp per entry).
    # Writes past the bound are dropped; ev_count keeps counting so the
    # exporter can surface the drop. Size it at roughly
    # 6 + max_prompt_len / prefill_chunk_tokens (chunk events dominate).
    telemetry_events_per_slot: int = 16
    # --- tensor parallelism (SPMD persistent window) ----------------------
    # Size of the ``model`` mesh axis the persistent window runs over:
    # attention heads and the paged KV pool are sharded across this many
    # devices (distribution.sharding head-partition rules) while ring /
    # allocator / scheduler / telemetry state stays replicated, so every
    # policy decision is computed identically on all shards. 1 = the
    # single-device engine (no mesh is built). Must divide the model's
    # num_kv_heads (make_model validates against the concrete arch);
    # incompatible with kv_fused_layout, whose interleaved pool has no
    # per-shard layout.
    mesh_model_size: int = 1

    def __post_init__(self):
        if self.prefill_chunk_tokens < 0:
            raise ValueError(
                f"prefill_chunk_tokens must be >= 0, got "
                f"{self.prefill_chunk_tokens}")
        if self.prefill_chunk_tokens > 0:
            if self.max_prefills_per_step < 1:
                raise ValueError(
                    f"max_prefills_per_step must be >= 1 under the "
                    f"mixed-phase scheduler, got {self.max_prefills_per_step}")
            if self.prefill_chunk_tokens > self.max_prompt_len:
                raise ValueError(
                    f"prefill_chunk_tokens={self.prefill_chunk_tokens} "
                    f"exceeds max_prompt_len={self.max_prompt_len}; a chunk "
                    f"larger than any prompt is the phase-exclusive "
                    f"scheduler with extra compile shapes")
            if (self.prefill_chunk_tokens > self.prefill_block_q
                    and self.prefill_chunk_tokens % self.prefill_block_q):
                raise ValueError(
                    f"prefill_chunk_tokens={self.prefill_chunk_tokens} is "
                    f"not a multiple of prefill_block_q="
                    f"{self.prefill_block_q}: the flash-prefill kernel "
                    f"tiles queries at block_q, so a ragged last tile "
                    f"burns a full tile of compute every chunk")
        if self.prefill_chunk_tokens_max < 0:
            raise ValueError(
                f"prefill_chunk_tokens_max must be >= 0, got "
                f"{self.prefill_chunk_tokens_max}")
        if self.prefill_chunk_tokens_max > 0:
            if self.prefill_chunk_tokens <= 0:
                raise ValueError(
                    "prefill_chunk_tokens_max (adaptive chunk sizing) "
                    "requires the mixed-phase scheduler: set "
                    "prefill_chunk_tokens > 0")
            if self.prefill_chunk_tokens_max < self.prefill_chunk_tokens:
                raise ValueError(
                    f"prefill_chunk_tokens_max="
                    f"{self.prefill_chunk_tokens_max} is below "
                    f"prefill_chunk_tokens={self.prefill_chunk_tokens}; "
                    f"the adaptive ceiling must cover the static chunk")
            if self.prefill_chunk_tokens_max < self.prefill_block_q:
                raise ValueError(
                    f"prefill_chunk_tokens_max="
                    f"{self.prefill_chunk_tokens_max} is below the "
                    f"adaptive floor prefill_block_q="
                    f"{self.prefill_block_q} (the budget range "
                    f"[prefill_block_q, prefill_chunk_tokens_max] would "
                    f"be empty)")
            if self.prefill_chunk_tokens_max % self.prefill_block_q:
                raise ValueError(
                    f"prefill_chunk_tokens_max="
                    f"{self.prefill_chunk_tokens_max} is not a multiple "
                    f"of prefill_block_q={self.prefill_block_q}: adaptive "
                    f"budgets are floor-aligned to whole kernel tiles")
            if self.prefill_chunk_tokens_max > self.max_prompt_len:
                raise ValueError(
                    f"prefill_chunk_tokens_max="
                    f"{self.prefill_chunk_tokens_max} exceeds "
                    f"max_prompt_len={self.max_prompt_len}; a ceiling "
                    f"larger than any prompt only adds compile shapes")
        if self.slo_classes < 1:
            raise ValueError(
                f"slo_classes must be >= 1, got {self.slo_classes}")
        if self.deadline_policy not in ("none", "ttft", "e2e"):
            raise ValueError(
                f"deadline_policy must be one of 'none'/'ttft'/'e2e', got "
                f"{self.deadline_policy!r}")
        if self.deadline_policy != "none":
            if self.prefill_chunk_tokens <= 0:
                raise ValueError(
                    "deadline_policy requires the mixed-phase scheduler "
                    "(prefill_chunk_tokens > 0): deadline cancellation is "
                    "a per-step policy decision and the phase-exclusive "
                    "engine has no per-step policy point")
            if len(self.slo_ttft_steps) != self.slo_classes:
                raise ValueError(
                    f"deadline_policy={self.deadline_policy!r} needs one "
                    f"slo_ttft_steps entry per class: got "
                    f"{len(self.slo_ttft_steps)} for slo_classes="
                    f"{self.slo_classes}")
            if any(t <= 0 for t in self.slo_ttft_steps):
                raise ValueError(
                    f"slo_ttft_steps entries must be positive, got "
                    f"{self.slo_ttft_steps}")
        if self.deadline_policy == "e2e":
            if len(self.slo_tpot_steps) != self.slo_classes:
                raise ValueError(
                    f"deadline_policy='e2e' needs one slo_tpot_steps entry "
                    f"per class: got {len(self.slo_tpot_steps)} for "
                    f"slo_classes={self.slo_classes}")
            if any(t <= 0 for t in self.slo_tpot_steps):
                raise ValueError(
                    f"slo_tpot_steps entries must be positive, got "
                    f"{self.slo_tpot_steps}")
        if self.slo_preempt:
            if self.prefill_chunk_tokens <= 0:
                raise ValueError(
                    "slo_preempt requires the mixed-phase scheduler "
                    "(prefill_chunk_tokens > 0): the preemption decision "
                    "runs at the top of every mixed step")
            if self.slo_classes < 2:
                raise ValueError(
                    "slo_preempt requires slo_classes >= 2: preemption "
                    "only ever evicts a STRICTLY lower class, so with one "
                    "class there is never an eligible victim")
        if self.intake_queue_limit < 0:
            raise ValueError(
                f"intake_queue_limit must be >= 0, got "
                f"{self.intake_queue_limit}")
        if self.prefix_trie_max_bytes < 0:
            raise ValueError(
                f"prefix_trie_max_bytes must be >= 0, got "
                f"{self.prefix_trie_max_bytes}")
        if self.prefix_trie_max_bytes > 0 and not self.prefix_cache:
            raise ValueError(
                "prefix_trie_max_bytes bounds the radix prefix trie; it "
                "requires prefix_cache=True")
        if self.watchdog_steps < 0:
            raise ValueError(
                f"watchdog_steps must be >= 0 (0 = watchdog off), got "
                f"{self.watchdog_steps}")
        if self.watchdog_steps > 0 and self.prefill_chunk_tokens <= 0:
            raise ValueError(
                "watchdog_steps requires the mixed-phase scheduler "
                "(prefill_chunk_tokens > 0): the watchdog is a per-step "
                "policy decision and the phase-exclusive engine has no "
                "per-step policy point")
        if self.snapshot_every_steps < 0:
            raise ValueError(
                f"snapshot_every_steps must be >= 0 (0 = no snapshots), "
                f"got {self.snapshot_every_steps}")
        if self.snapshot_every_steps > 0 and (
                self.snapshot_every_steps % self.window):
            raise ValueError(
                f"snapshot_every_steps={self.snapshot_every_steps} is not "
                f"a multiple of window={self.window}: snapshots are taken "
                f"by the DPU plane and only window boundaries exist there")
        if self.telemetry_events_per_slot < 1:
            raise ValueError(
                f"telemetry_events_per_slot must be >= 1 (every request "
                f"logs at least its submission), got "
                f"{self.telemetry_events_per_slot}")
        if self.attn_unified and self.prefill_chunk_tokens <= 0:
            raise ValueError(
                "attn_unified requires the mixed-phase scheduler "
                "(prefill_chunk_tokens > 0): the unified dispatch merges "
                "the chunk-prefill and decode branches of the mixed step, "
                "and the phase-exclusive engine has neither")
        if self.kv_fused_layout:
            if not self.attn_unified:
                raise ValueError(
                    "kv_fused_layout (interleaved K/V pages) requires "
                    "attn_unified: only the unified ragged kernel and the "
                    "gather reference read the fused layout — the split "
                    "paged-attention / flash-prefill kernels do not")
            if self.slo_preempt:
                raise ValueError(
                    "kv_fused_layout is incompatible with slo_preempt: the "
                    "KV offload/restore path copies split k_pages/v_pages "
                    "pools host-side")
        if self.mesh_model_size < 1:
            raise ValueError(
                f"mesh_model_size must be >= 1 (1 = single device), got "
                f"{self.mesh_model_size}")
        if self.mesh_model_size > 1 and self.kv_fused_layout:
            raise ValueError(
                "mesh_model_size > 1 is incompatible with kv_fused_layout: "
                "the interleaved K/V page pool fuses the head dimension "
                "into the page row, so it has no per-shard layout on the "
                "model axis — use the split k_pages/v_pages pools")

    def deadline_steps(self, slo_class: int, max_new: int):
        """Relative deadline (engine steps from submission) for a request
        of class ``slo_class`` generating ``max_new`` tokens, or None when
        the deadline policy is off. Submitters add the current step to get
        the absolute ``RingState.deadline_step``."""
        if self.deadline_policy == "none":
            return None
        ttft = self.slo_ttft_steps[slo_class]
        if self.deadline_policy == "ttft":
            return int(ttft)
        return int(ttft + self.slo_tpot_steps[slo_class] * max_new)

    @property
    def max_seq(self) -> int:
        return self.max_prompt_len + self.max_new_tokens

    @property
    def chunk_bucket(self) -> int:
        """Compiled token width of the mixed-step chunk dispatch: the
        adaptive ceiling when adaptive sizing is on, else the static chunk.
        (The per-iteration budget only clamps how many of these columns are
        live — the program shape never changes.)"""
        return self.prefill_chunk_tokens_max or self.prefill_chunk_tokens

    @property
    def pages_per_req(self) -> int:
        return (self.max_seq + self.page_size - 1) // self.page_size
