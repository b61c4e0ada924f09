"""Config dataclasses for the architecture zoo and the framework."""

from __future__ import annotations

import dataclasses
from typing import Literal

from repro.core.precision import PrecisionPolicy
from repro.core.quant import QuantConfig


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2 style; minicpm3)."""

    q_lora_rank: int = 768
    kv_lora_rank: int = 256
    qk_nope_head_dim: int = 64
    qk_rope_head_dim: int = 32
    v_head_dim: int = 64


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_expert: int  # per-expert FFN hidden dim
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    router_z_weight: float = 1e-3


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """Mamba2 / SSD (arXiv:2405.21060)."""

    state_dim: int = 128
    head_dim: int = 64
    expand: int = 2
    conv_width: int = 4
    chunk_size: int = 64
    n_groups: int = 1

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def n_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    """Zamba2-style hybrid: Mamba2 backbone + shared attention block."""

    attn_every: int = 6  # shared attn applied at layer_idx % attn_every == 0
    concat_residual: bool = True  # shared block sees concat(x, x_embed)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: Literal["dense", "moe", "hybrid", "ssm", "vlm", "audio"]
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int | None = None  # default d_model // n_heads
    attn_kind: Literal["gqa", "mla", "none"] = "gqa"
    norm_kind: Literal["rmsnorm", "layernorm"] = "rmsnorm"
    act: Literal["silu", "gelu"] = "silu"
    gated_mlp: bool = True
    rope_theta: float = 10000.0
    sliding_window: int | None = None
    mla: MLAConfig | None = None
    moe: MoEConfig | None = None
    ssm: SSMConfig | None = None
    hybrid: HybridConfig | None = None
    frontend: Literal["patch", "audio"] | None = None
    frontend_dim: int = 0  # stub modality embedding dim (0 = d_model)
    n_frontend_tokens: int = 0
    is_encoder: bool = False
    tie_embeddings: bool = False
    attn_bias: bool = False
    mlp_bias: bool = False
    use_rope: bool = True  # physics models use learned positions instead
    # muP-style scaling (MiniCPM): scale_emb, scale_depth, dim_model_base
    emb_scale: float = 1.0
    residual_scale: float = 1.0  # applied to each residual branch
    logit_scale: float = 1.0
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    # legacy per-model quantization knobs; lowered onto `precision` when set
    quant: QuantConfig = dataclasses.field(default_factory=QuantConfig)
    # declarative per-layer precision: a PrecisionPolicy or preset name
    # (core/precision.py); None = fall back to the legacy `quant` shim
    precision: PrecisionPolicy | str | None = None
    # recommended serving preset for this arch (`--policy auto` in the
    # launchers resolves to this)
    serve_policy: str = "float"
    # paper-style extras (physics models)
    input_vec_size: int = 0  # continuous-input models (paper's three)
    seq_len: int = 0  # fixed seq for physics models
    n_classes: int = 0
    pool: Literal["mean", "last", "none"] = "none"

    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim is not None:
            return self.head_dim
        return self.d_model // self.n_heads

    @property
    def padded_vocab_size(self) -> int:
        """Vocab padded to a multiple of 256 for clean TP sharding."""
        return ((self.vocab_size + 255) // 256) * 256

    def param_count_estimate(self) -> int:
        """Rough 6ND-style N (for MODEL_FLOPS; exact count via params.py)."""
        d, l = self.d_model, self.n_layers
        emb = self.padded_vocab_size * d
        if self.attn_kind == "mla" and self.mla is not None:
            m = self.mla
            qk = m.qk_nope_head_dim + m.qk_rope_head_dim
            attn = (
                d * m.q_lora_rank
                + m.q_lora_rank * self.n_heads * qk
                + d * (m.kv_lora_rank + m.qk_rope_head_dim)
                + m.kv_lora_rank
                * self.n_heads
                * (m.qk_nope_head_dim + m.v_head_dim)
                + self.n_heads * m.v_head_dim * d
            )
        elif self.attn_kind == "none":
            attn = 0
        else:
            hd = self.resolved_head_dim
            attn = d * hd * (self.n_heads + 2 * self.n_kv_heads) + self.n_heads * hd * d
        if self.family == "hybrid" and self.ssm is not None:
            # Mamba2 backbone layers + one weight-shared attention block
            s = self.ssm
            di = s.d_inner(d)
            per_mamba = (
                d * (2 * di + 2 * s.n_groups * s.state_dim + s.n_heads(d))
                + di * d
            )
            w = 2 * d  # shared block works in concat(x, x_embed) width
            ff_mult = 3 if self.gated_mlp else 2
            shared = 4 * w * w + ff_mult * w * self.d_ff + w * d
            return emb + l * per_mamba + shared + (
                0 if self.tie_embeddings else emb
            )
        if self.moe is not None:
            ff_mult = 3 if self.gated_mlp else 2
            ffn = self.moe.n_experts * ff_mult * d * self.moe.d_expert
        elif self.ssm is not None and self.attn_kind == "none":
            s = self.ssm
            di = s.d_inner(d)
            ffn = d * (2 * di + 2 * s.n_groups * s.state_dim + s.n_heads(d)) + di * d
        else:
            ff_mult = 3 if self.gated_mlp else 2
            ffn = ff_mult * d * self.d_ff
        return emb + l * (attn + ffn) + (0 if self.tie_embeddings else emb)

    def active_param_count_estimate(self) -> int:
        """Active params per token (MoE: top_k experts only)."""
        if self.moe is None:
            return self.param_count_estimate()
        dense_like = dataclasses.replace(self, moe=None, d_ff=0, gated_mlp=False)
        base = dense_like.param_count_estimate()
        ff_mult = 3 if self.gated_mlp else 2
        active_ffn = (
            self.n_layers * self.moe.top_k * ff_mult * self.d_model * self.moe.d_expert
        )
        return base + active_ffn


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One assigned input-shape cell."""

    name: str
    seq_len: int
    global_batch: int
    kind: Literal["train", "prefill", "decode"]


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


@dataclasses.dataclass(frozen=True)
class ParallelismConfig:
    """Logical-axis -> mesh-axes mapping knobs (see distributed/sharding)."""

    dp: bool = True  # batch over ('pod','data')
    fsdp: bool = True  # weight non-TP axis over 'data'
    tp: bool = True  # heads/mlp/vocab over 'model'
    ep: bool = True  # experts over 'model'
    sp: bool = False  # sequence over 'model' (long-context cells)
    remat: Literal["none", "minimal", "full"] = "minimal"
    grad_accum: int = 1  # microbatch accumulation (activation memory / k)


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 3e-4
    weight_decay: float = 0.1
    warmup_steps: int = 100
    total_steps: int = 1000
    schedule: Literal["cosine", "wsd", "linear"] = "cosine"
    decay_fraction: float = 0.1  # WSD decay phase fraction
    grad_clip: float = 1.0
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    checkpoint_every: int = 200
    keep_checkpoints: int = 3
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_batch: int = 8
    max_seq_len: int = 1024
    # Engine-default softmax temperature (0.0 = greedy).  A per-request
    # SamplingParams.temperature overrides it; the knobs ride the
    # compiled programs as traced per-slot arrays (serve/sampling.py),
    # so mixing greedy and sampled requests in one batch mints no extra
    # programs — the len(prefill_buckets)+2 jit budget is unchanged and
    # test-enforced.
    temperature: float = 0.0
    # Declarative serving precision: a PrecisionPolicy, a preset name
    # ("int8_serve", "paper_vu13p", "qat_fixed<12,6>", ...), or None.
    # (The legacy int8_weights/int8_kv_cache/lut_softmax booleans were
    # removed after their deprecation cycle; see README "Precision
    # policies" for the migration table.)
    policy: PrecisionPolicy | str | None = None
    # --- KV-cache layout (serve/kv_cache.py CacheManager) ---
    # "dense": per-slot slabs of max_seq_len tokens (the historical
    # layout).  "paged": block-table-indexed pages — long contexts
    # allocate on demand, freed slots return pages immediately.  Families
    # whose caches are not position-addressed (SSM/hybrid, rolling
    # sliding-window) fall back to dense automatically.
    kv_layout: Literal["dense", "paged"] = "dense"
    # Tokens per page (paged layout); must divide max_seq_len so every
    # slot's logical view is a whole number of fixed-stride pages.
    kv_page_size: int = 16
    # Physical pages in the pool (paged layout).  None = enough for every
    # slot at full length plus the reserved trash page (no oversubscription);
    # set lower to oversubscribe memory for long-max_seq_len workloads.
    kv_pages: int | None = None
    # Prefix-cache page sharing (paged layout only).  Full prompt pages are
    # hash-chained into a prefix index; a same-prefix admission maps its
    # leading block-table entries to the already-filled pages (refcounted)
    # instead of allocating and filling fresh ones.  Finished requests'
    # registered pages are retained (refcount 0, evictable LRU) so repeated
    # prompts keep hitting after their first tenant completes.  Decode
    # writes into a shared page copy-on-write a private copy first, so
    # every logit stays bit-identical to the dense layout — greedy
    # (temperature=0) token streams are bit-identical too,
    # test-enforced.  Unseeded sampled (temperature>0) streams are
    # equally distributed but not reproducible against a dense run:
    # skipping a prefill dispatch reshuffles which engine PRNG key
    # samples which token.  Requests with an explicit
    # SamplingParams.seed are exempt — their streams are keyed by
    # (seed, position) and survive any rescheduling (test-enforced).
    # A hit additionally skips the prompt-prefill dispatch (prefill-skip):
    # bit-exact float-GQA engines teacher-force the uncovered tail through
    # the decode program, every other datapath (MLA, int8 KV, LUT softmax)
    # replays it through the cache-extending prefill program (see
    # ``cache_extend`` and the README datapath-capability matrix).
    # No-op for the dense layout.
    kv_prefix_cache: bool = False
    # Page-aware preemption (paged layout only).  When the page pool cannot
    # cover the queue head's reservation, preempt the youngest resident
    # request — free its private pages and re-queue it at the queue front
    # with prompt + generated-so-far as a resumable prompt — instead of
    # head-of-line blocking until pages drain.  A resume replays the prompt
    # part through prefill math (whole-prompt dispatch on bit-exact float
    # GQA; the cache-extending prefill program elsewhere) and the
    # generated part through the teacher-forced decode scan — the same
    # math that originally wrote each position — so greedy token streams
    # stay identical to the unpreempted run on every datapath (see the
    # README datapath-capability matrix).  The identity guarantee is on
    # logits and greedy token streams; a resume changes the PRNG
    # dispatch schedule for unseeded sampled decoding (seeded requests
    # are position-keyed and reproduce exactly, test-enforced).
    kv_preemption: bool = False
    # --- tiered KV cache: host-memory victim tier (paged + prefix cache) ---
    # Host-memory pages backing the prefix cache.  When > 0 (and
    # ``kv_victim_tier`` is on), a registered page evicted off the device
    # LRU under pool pressure spills its pool rows (k/v, int8 scale, MLA
    # latent pools alike) into a pinned host-side numpy ring of this many
    # pages instead of being discarded, keeping its prefix-index chain
    # key alive.  A later same-prefix admission that walks past device
    # coverage into the host tier swaps the spilled rows back into fresh
    # device pages — one batched host->device copy applied at the next
    # dispatch (``CacheManager.flush_swaps``, next to the CoW flush) —
    # and admits as a normal prefix hit with prefill-skip, so a warm
    # prefix larger than the device pool costs a page copy instead of a
    # recompute.  Spilled pages survive their tenant's finish (and the
    # device eviction) but not a process restart.  0 = no victim tier
    # (evictions discard, the pre-tier behavior).  Requires the paged
    # layout with kv_prefix_cache; silently inert otherwise.
    kv_host_pages: int = 0
    # Kill switch for the victim tier: with False, kv_host_pages is
    # ignored and evictions discard pages exactly as before.  Split from
    # kv_host_pages so deployments can size the ring in config and flip
    # the tier off operationally.
    kv_victim_tier: bool = True
    # --- engine v2: bucketed prefill + scan decode ---
    # Prompt-length buckets for prefill padding.  None = auto powers of two
    # up to max_seq_len; () = exact-length prefill (the v1 behavior, one
    # compiled program per distinct prompt length).
    prefill_buckets: tuple[int, ...] | None = None
    # Decode tokens generated per host dispatch (lax.scan over the fused
    # decode program).  1 = the v1 one-token-per-step path.
    decode_steps: int = 4
    # Max prompts admitted (prefilled) per engine step; 0 = fill every
    # free slot (v1 behavior).
    max_prefill_per_step: int = 0
    # --- chunked prefill (scheduler policy; serve/scheduler.py) ---
    # When set, a prompt longer than this admits by prefilling only its
    # first `prefill_chunk` tokens through the bucketed prefill program
    # and replaying the remaining prompt tail incrementally — teacher-
    # forced through the decode scan on bit-exact float-GQA engines,
    # chunk-at-a-time through the cache-extending prefill program on
    # every other datapath (MLA, int8 KV, LUT softmax; see
    # ``cache_extend``) — interleaved with resident decode steps, so
    # admitting a long prompt stalls resident decoding by at most a
    # chunk-sized dispatch instead of a full-prompt-sized one.  Greedy
    # token streams stay identical to unchunked on every datapath
    # (test-enforced; README datapath-capability matrix).  Must not
    # exceed the largest prefill bucket (the chunk dispatch reuses a
    # bucketed program), and requires a bucketable (position-addressed)
    # cache: setting it on SSM/hybrid or rolling sliding-window engines
    # is a configuration error.  None = off.
    prefill_chunk: int | None = None
    # Cache-extending prefill program (serve/executor.py).  One extra
    # jitted program — fixed shape (max_batch, window) — that runs the
    # prefill-path forward over a token window against the already-
    # populated caches, scattering new K/V through the dense/paged
    # write machinery.  Replayed tokens go through the same math that
    # produced the cache, which is what lets chunked prefill,
    # prefix-skip, and preemption-resume activate on datapaths whose
    # decode scan is NOT bit-exact with prefill (MLA latent caches,
    # int8 KV, LUT softmax).  Costs one compiled program on those
    # engines (len(prefill_buckets) + 2 total, CI-enforced); engines on
    # the Pallas kernel or without a bucketable cache fall back to the
    # legacy bit-exact gating.  Disable to restore the pre-extend
    # behavior (quantized datapaths silently skip the optimizations).
    cache_extend: bool = True
    # --- speculative decoding (serve/executor.py DraftWorker) ---
    # A small draft model greedily proposes up to ``spec_tokens`` tokens
    # per sampling-ready resident slot; the target model verifies the
    # whole proposal in ONE cache-extending prefill dispatch
    # (accept-prefix + one correction token).  Rejected drafts rewind
    # through the existing window-write machinery: extend writes are
    # position-idempotent, so the stale tail is simply overwritten by
    # the next accepted window.  Requires the cache-extending prefill
    # program (``cache_extend``); silently off (with a warning) where
    # that program is unavailable.  The target's jit budget is unchanged
    # — the draft model adds its own bounded program set (at most
    # len(prefill_buckets) draft prefills + 1 propose scan,
    # CI-enforced).  Greedy (temperature=0) token streams are bitwise
    # identical to non-speculative decoding on bit-exact datapaths
    # (test-enforced); per-request acceptance counters land in
    # telemetry.
    speculative: bool = False
    # Draft tokens proposed per verification window; clamped to the
    # extend program's window width.
    spec_tokens: int = 4
    # Zoo config name for the draft model (resolved by the Engine, which
    # initializes fresh params for it — pass explicit draft params via
    # ``Engine(draft=...)`` for a trained draft).  None/"self" = use the
    # target model as its own draft: acceptance approaches 1.0, which
    # exercises the full verify/rewind machinery and bounds the
    # best-case speedup, without needing a second trained model.
    draft_config: str | None = None
    # --- SLO-aware scheduling (serve/slo.py DeadlineScheduler) ---
    # Scheduling policy the engine builds when no explicit
    # ``scheduler_factory`` is passed.  "fifo": the historical
    # FifoScheduler.  "edf": earliest-deadline-first — the queue is
    # kept sorted by each request's absolute deadline (deadline-less
    # requests run FIFO behind every deadlined one), preemption picks
    # the least-urgent resident, and ``overdue_policy`` decides what
    # happens to a request whose deadline passes while it is still
    # queued.  True to the paper's hard-real-time physics-trigger
    # context, where past-deadline work is worthless.
    scheduler: Literal["fifo", "edf"] = "fifo"
    # Default per-request deadline in milliseconds, measured from
    # submit time; a request submitted without an explicit
    # ``deadline_s`` inherits it.  None = requests carry no deadline
    # unless they ask for one.
    deadline_ms: float | None = None
    # What the EDF scheduler does with a *queued* request whose deadline
    # already passed: "drop" removes it (the client streams a terminal
    # event with finish_reason="deadline" and its capacity is spent on
    # feasible work), "demote" moves it behind every still-feasible
    # request, "ignore" leaves pure EDF order.  Residents past deadline
    # always run to completion (counted as misses, never corrupted).
    overdue_policy: Literal["drop", "demote", "ignore"] = "drop"
    # --- step-phase tracing (serve/phases.py PhaseTracer) ---
    # Break each engine step into schedule / host_prep / dispatch /
    # device / sample timings (device time isolated by fencing every
    # dispatch with block_until_ready).  Off by default: the fenced
    # path serializes host and device work, so production throughput
    # measurements must opt in deliberately.  Per-step records land in
    # a ring buffer; p50/p95/p99 summaries under
    # ``Engine.telemetry["phases"]``.
    trace_phases: bool = False
    # Per-step records retained by the tracer's ring buffer.
    phase_ring: int = 512
    # Tracer flavor (serve/phases.py).  "fenced": the PR-7 tracer —
    # block_until_ready after every dispatch isolates device time
    # exactly, at the cost of serializing host and device (it measures a
    # pipeline it also destroys).  "overlap": never fences; instead it
    # reports ``device_overlap_s`` (the host-side span between a decode
    # dispatch returning and its collect starting — device time hidden
    # under host work), ``host_bubble_s`` (the residual blocking wait in
    # collect — host time NOT hidden), and ``overlap_efficiency`` =
    # overlap / (overlap + bubble).  The only mode that can measure the
    # async loop without un-pipelining it.
    phase_mode: Literal["fenced", "overlap"] = "fenced"
    # --- pipelined async engine loop (serve/api.py) ---
    # Double-buffered engine loop: while step N's decode dispatch is in
    # flight on device, the scheduler computes step N+1's decision and
    # the host preps its inputs, so schedule/host_prep/sample hide under
    # device time.  The executor splits into a non-blocking
    # ``dispatch(decision) -> InflightStep`` and a blocking
    # ``collect(inflight) -> StepOutput``; the device->host transfer of
    # sampled tokens is deferred one step, and the sampled-token carry
    # for step N+1's decode scan stays on device (no host round-trip
    # between consecutive decode dispatches).  Greedy (temperature=0)
    # token streams are bit-identical to the synchronous loop on every
    # datapath/layout (test-enforced); unseeded sampled streams are
    # equally distributed but may diverge (the dispatch schedule
    # reshuffles PRNG key splits, same caveat as prefix-skip and
    # preemption; seeded requests are position-keyed and reproduce
    # exactly).  Cancels
    # and EDF deadline drops act at a one-step-stale boundary: up to one
    # in-flight dispatch's tokens for a cancelled request are discarded,
    # and preemption victims are only picked among fully-collected slots
    # (see README "Async engine loop & mesh sharding").  Off by default:
    # the legacy loop runs byte-identical code.
    async_loop: bool = False
    # --- mesh-sharded serving (distributed/sharding.py) ---
    # Tensor parallel over the devices the params are committed to: a
    # (data 1, model n) mesh (launch/mesh.tensor_parallel_mesh), params
    # and KV caches placed by ShardingRules / cache_shardings (heads, KV
    # heads, FFN columns and the vocabulary split n ways; the paged pools
    # over kv_heads, built in place), so every prefill/extend/decode
    # program compiles against sharding-annotated operands.  Params on
    # one device give the identity placement (token streams
    # bit-identical, jit budget unchanged — both test-enforced).
    shard_decode: bool = False
    # Data-parallel replica fan-out (serve/router.py ReplicaRouter):
    # N independent engines behind one queue with least-loaded
    # admission.  1 = a single engine, no router.  Each replica holds
    # its own KV pool and jit caches (len(prefill_buckets)+2 programs
    # per replica — the budget is per engine, not per process).
    replicas: int = 1

    def resolved_buckets(self) -> tuple[int, ...]:
        """Prefill buckets, ascending.  Auto mode: powers of two in
        [8, max_seq_len]."""
        if self.prefill_buckets is not None:
            return tuple(sorted(self.prefill_buckets))
        buckets, b = [], 8
        while b < self.max_seq_len:
            buckets.append(b)
            b *= 2
        buckets.append(self.max_seq_len)
        return tuple(buckets)
