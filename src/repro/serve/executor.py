"""Execution layer of the serving stack: *device work only, no policy*.

:class:`ModelExecutor` owns everything that touches the accelerator —
the precision plan applied to the params, the per-bucket prefill jit
cache and the single decode-scan program, the
:class:`~repro.serve.kv_cache.CacheManager` with its device cache
pytree, and the slot table (execution state: write positions, carry
tokens, pending teacher-forced tails).  It consumes an explicit
:class:`~repro.serve.scheduler.ScheduleDecision` and mechanically
applies it: reset preempted slots, activate admissions, run one
fixed-shape prefill dispatch per bucket group, run the decode scan,
retire finished slots.  Every *choice* (who is admitted where, who
preempts, what chunks) was already made by the scheduler; the executor
never inspects the queue and never makes a policy decision.

The compiled-program discipline: at most ``len(prefill_buckets)``
prefill programs (each at the fixed ``max_batch`` width) plus one
decode program plus — on datapaths that need it — one cache-extending
prefill program, test-enforced on the real jit caches.  The extend
program runs the prefill-path forward over a fixed-width token window
against the already-populated caches, so prefill-skip tails, chunk
tails, and preemption-resume prompts can be replayed with the same
math that produced the cache even when the decode scan is not bitwise
the prefill (MLA latent caches, int8 KV, LUT softmax).
"""

from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ServeConfig
from repro.core import precision as precision_lib
from repro.models import lm
from repro.serve import kv_cache
from repro.serve.phases import NULL_TRACER
from repro.serve.sampling import sample_tokens
from repro.serve.scheduler import (
    MODE_FORK,
    MODE_SKIP,
    Admission,
    ExecutorCaps,
    Request,
    ScheduleDecision,
    Slot,
    encode_sampling,
)

PyTree = Any


@dataclasses.dataclass
class StepOutput:
    """What one executed decision produced, for the API layer to route:
    ``tokens`` are (uid, token, index-in-generated) in emission order,
    ``finished``/``preempted`` the requests that left their slots."""

    stats: dict
    tokens: list[tuple[int, int, int]] = dataclasses.field(default_factory=list)
    finished: list[Request] = dataclasses.field(default_factory=list)
    preempted: list[Request] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class InflightStep:
    """A dispatched-but-uncollected engine step (the async loop's
    double-buffer token).  ``out`` already holds everything the
    internally-synchronous prefill/extend paths produced (they sample on
    host); the decode scan's results are still on device in ``dev``
    until :meth:`ModelExecutor.collect` blocks on them.

    ``snapshot`` pins each decode slot's Request at dispatch time so a
    mid-flight ``cancel`` (or any slot turnover) is detected at collect:
    a slot whose request changed identity — or was cancelled — has its
    in-flight tokens discarded rather than routed to a dead stream."""

    out: StepOutput
    decision: ScheduleDecision
    #: slots the decode dispatch covered (sorted), () = no decode ran
    decode_set: tuple[int, ...] = ()
    #: device arrays (toks_t, emit_t, tok_f, pos_f, act_f, rem_f) from
    #: the decode scan; None when no decode ran
    dev: Any = None
    #: slot index -> Request resident there when the scan was dispatched
    snapshot: dict[int, Request] = dataclasses.field(default_factory=dict)
    #: slot index -> the slot's admission stamp at dispatch; catches the
    #: turnover the Request identity check cannot — the SAME request
    #: preempted mid-flight and re-admitted into the SAME slot (its
    #: resume replay was planned from pre-dispatch ``generated``, so the
    #: in-flight tokens must still be discarded)
    admit_seqs: dict[int, int] = dataclasses.field(default_factory=dict)
    #: per-slot forced-token counts the dispatch consumed (host array)
    n_forced: Any = None
    #: monotone dispatch stamp: collect only clears a slot's inflight
    #: mark when no newer dispatch has re-marked it (the pipelined loop
    #: dispatches N+1 before collecting N, often over the same slots)
    seq: int = 0
    #: tracer stamp of the decode dispatch's return (overlap accounting)
    t_dispatch: float = 0.0
    #: perf_counter at decode dispatch start (decode_time_s accounting)
    t0: float = 0.0
    #: engine-clock stamp set by the Engine right after dispatch; token
    #: events and finished_at for this step carry it, keeping virtual-
    #: clock replay deterministic one step late (StepClock contract)
    dispatched_at: float | None = None

    @property
    def empty(self) -> bool:
        """Nothing to collect and nothing to route (idle dispatch)."""
        return (
            self.dev is None
            and not self.out.tokens
            and not self.out.finished
            and not self.out.preempted
        )


class DraftWorker:
    """The draft side of speculative decoding: a (small) model with its
    own dense float KV cache that greedily proposes ``spec_k`` tokens per
    resident slot in one scan dispatch.

    Program discipline mirrors the target executor's: at most
    ``len(buckets)`` draft prefill programs (used to resync a slot's
    draft cache from its token history after any host-side turnover)
    plus ONE propose-scan program, all at fixed ``(max_batch, ...)``
    shapes.  The draft cache is always dense float32 at
    ``max_batch x max_seq_len`` — the draft never pages and never
    quantizes, so its decode math is its own prefill math and resyncs
    are cheap and exact.

    ``pos[i]``/``tok[i]`` track which (position, carry token) the draft
    cache row i is synced to; ``-1`` means unsynced (the target
    executor's ``_host_dirty`` hook invalidates on every slot turnover).
    """

    def __init__(self, cfg, params, serve_cfg, buckets, spec_k):
        self.cfg = cfg
        self.params = params
        self.sc = serve_cfg
        self.buckets = tuple(buckets)
        self.spec_k = int(spec_k)
        nb = serve_cfg.max_batch
        self.caches = kv_cache.init_caches(
            cfg, nb, serve_cfg.max_seq_len, dtype=jnp.float32,
            quantized=False,
        )
        self.pos = [-1] * nb
        self.tok = [0] * nb
        self._prefill_fn: dict[int, Any] = {}
        self._propose_fn = jax.jit(self._propose_scan)

    def bucket_for(self, n: int) -> int | None:
        """Smallest draft prefill bucket covering ``n`` history tokens
        (None when the history outgrew every bucket — the slot simply
        decodes without speculation)."""
        for b in self.buckets:
            if b >= n:
                return b
        return None

    def _prefill_batch(self, params, tokens, lengths, caches, slots):
        """Rebuild draft cache rows from token histories in one bucketed
        dispatch (same row conventions as the target's prefill: pad rows
        carry length 0 and slot ``max_batch``, dropped by the dense
        scatter)."""
        nb, bucket = tokens.shape
        mask = jnp.arange(bucket, dtype=jnp.int32)[None, :] < lengths[:, None]
        tokens = jnp.where(mask, tokens, 0)
        small = kv_cache.init_caches(
            self.cfg, nb, self.sc.max_seq_len, dtype=jnp.float32,
            quantized=False,
        )
        _, filled, _ = lm.forward(
            params, self.cfg, {"tokens": tokens}, mode="prefill",
            caches=small,
        )
        filled = kv_cache.mask_cache_tail(filled, lengths)
        return kv_cache.insert_prefill_dense(caches, filled, slots)

    def _propose_scan(self, params, tokens, positions, active, caches):
        """Propose ``spec_k`` greedy tokens per active row in one scan.
        Row i processes its carry token at ``positions[i]`` (writing its
        KV) and argmaxes the next, exactly like the target decode scan
        minus sampling and emission bookkeeping.  Inactive rows freeze;
        their repeated same-position writes are idempotent."""
        def body(carry, _):
            tok, pos, c = carry
            logits, new_c, _ = lm.forward(
                params, self.cfg, {"tokens": tok[:, None]}, mode="decode",
                caches=c, positions=pos,
            )
            nxt = jnp.where(
                active,
                jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32),
                tok,
            )
            new_pos = jnp.where(active, pos + 1, pos)
            return (nxt, new_pos, new_c), nxt

        (tok, pos, caches), toks_t = jax.lax.scan(
            body, (tokens, positions, caches), None, length=self.spec_k
        )
        return toks_t, caches

    def sync(self, need: list[tuple[int, list[int]]], tel: dict) -> None:
        """Resync draft cache rows from their token histories, grouped by
        the smallest covering bucket.  ``need`` rows were pre-filtered to
        fit a bucket; empty histories just mark synced (nothing to
        write)."""
        groups: dict[int, list[tuple[int, list[int]]]] = {}
        for i, hist in need:
            if not hist:
                continue
            groups.setdefault(self.bucket_for(len(hist)), []).append((i, hist))
        nb = self.sc.max_batch
        for bucket in sorted(groups):
            grp = groups[bucket]
            toks = np.zeros((nb, bucket), np.int32)
            lengths = np.zeros((nb,), np.int32)
            slot_arr = np.full((nb,), nb, np.int32)
            for row, (i, hist) in enumerate(grp):
                toks[row, : len(hist)] = hist
                lengths[row] = len(hist)
                slot_arr[row] = i
            fn = self._prefill_fn.get(bucket)
            if fn is None:
                fn = jax.jit(self._prefill_batch)
                self._prefill_fn[bucket] = fn
                tel["draft_prefill_compiles"] = (
                    tel.get("draft_prefill_compiles", 0) + 1
                )
            self.caches = fn(
                self.params, jnp.asarray(toks), jnp.asarray(lengths),
                self.caches, jnp.asarray(slot_arr),
            )


class ModelExecutor:
    def __init__(
        self,
        cfg: ModelConfig,
        params: PyTree,
        serve_cfg: ServeConfig | None = None,
        kernel: dict | None = None,
        seed: int = 0,
        replica: int = 0,
        draft: tuple[ModelConfig, PyTree] | None = None,
    ):
        self.serve_cfg = serve_cfg or ServeConfig()
        if self.serve_cfg.decode_steps < 1:
            raise ValueError(
                f"decode_steps must be >= 1, got {self.serve_cfg.decode_steps}"
            )
        if self.serve_cfg.max_prefill_per_step < 0:
            raise ValueError(
                "max_prefill_per_step must be >= 0 (0 = fill all free slots)"
            )
        self.kernel = kernel or {}
        self.key = jax.random.PRNGKey(seed)
        # Replica salt: fold the replica index into the dispatch key so a
        # router's replicas draw distinct unseeded sampled streams even
        # when handed the same base seed.  fold_in (not seed + replica)
        # keeps (seed, replica) pairs collision-free.  Per-request seeded
        # streams are position-keyed and replica-independent by design,
        # so the salt leaves them untouched.
        self.replica = int(replica)
        if self.replica:
            self.key = jax.random.fold_in(self.key, self.replica)

        # Precision: one declarative policy governs weights (offline PTQ /
        # int8 quantize-dequantize; the true int8 GEMM path is
        # kernels/qmatmul on TPU), the KV-cache dtype, the softmax kernel
        # mode, and any runtime fake-quant the model applies in-graph.
        # ServeConfig.policy wins; otherwise the model's own policy applies.
        if self.serve_cfg.policy is not None:
            policy = precision_lib.get_policy(self.serve_cfg.policy)
            cfg = dataclasses.replace(cfg, precision=policy)
        else:
            policy = precision_lib.model_policy(cfg)
        self.cfg = cfg
        self.policy = policy
        self.plan = policy.resolve(cfg.n_layers)
        self.kernel = self.plan.kernel_defaults(self.kernel) or {}
        self.params = precision_lib.apply_plan_to_params(params, self.plan)

        if self.plan.int8_kv_cache and self.plan.kv_cache.bits != 8:
            raise NotImplementedError(
                "the KV cache implements 8-bit per-token quantization only; "
                f"policy {self.policy.name!r} asks for "
                f"{self.plan.kv_cache.bits}-bit"
            )
        sc = self.serve_cfg
        self.quant_cache = bool(
            self.plan.int8_kv_cache
            and cfg.attn_kind in ("gqa", "mla")
            and cfg.family not in ("ssm", "hybrid")
        )
        # All layout knowledge (dense slabs vs block-table pages, specs,
        # insertion, allocation) lives in the manager.
        self.cache_mgr = kv_cache.CacheManager(
            cfg, sc, quantized=self.quant_cache, dtype=jnp.float32
        )
        self.kv_layout = self.cache_mgr.layout
        self.caches = self.cache_mgr.init_device_caches()
        self.slots = [Slot() for _ in range(sc.max_batch)]
        #: pipelined loop (ServeConfig.async_loop): dispatch and collect
        #: interleave across steps, and the decode carry stays on device
        self.async_loop = bool(sc.async_loop)

        # Mesh-sharded decode: place params and the KV pools with
        # NamedSharding over a (data, model) host mesh so every
        # prefill/extend/decode program compiles against sharding-
        # annotated operands.  Committed input shardings propagate
        # through the existing jitted programs — no new programs, so the
        # len(buckets)+2 budget holds (test-enforced).  The page table
        # keeps its sharding across host-side rebuilds via the manager's
        # ``table_sharding`` hook (a fresh uncommitted table would
        # otherwise re-key the jit cache and mint a second decode
        # program mid-run).
        self.mesh = None
        self.sharding_rules = None
        self._cache_out_sh = None
        self._rep_sh = None
        if sc.shard_decode:
            from repro.distributed import sharding as sharding_lib
            from repro.launch.mesh import make_host_mesh

            self.mesh = make_host_mesh()
            rules = sharding_lib.ShardingRules(self.mesh)
            self.sharding_rules = rules
            self.params = jax.device_put(
                self.params, sharding_lib.param_shardings(rules, cfg, lm)
            )
            cache_sh = self.cache_mgr.device_shardings(rules)
            self.caches = jax.device_put(self.caches, cache_sh)
            table_sh = cache_sh.get("layers", {}).get("page_table")
            if table_sh is not None:
                self.cache_mgr.table_sharding = table_sh
            # pinning every program's cache outputs to the SAME shardings
            # the pools were placed with (and the small per-slot arrays to
            # a replicated placement) is what keeps the jit caches at one
            # program each: left to GSPMD, a program's chosen output
            # sharding (e.g. the page table partitioned over 'model') can
            # differ from the host-side placement, and the next dispatch
            # would re-key on the flip-flopping operand sharding
            self._cache_out_sh = cache_sh
            self._rep_sh = jax.sharding.NamedSharding(
                self.mesh, jax.sharding.PartitionSpec()
            )

        # Device-resident decode carry (async loop): the scan's final
        # (token, position, active, budget) per slot, kept on device so
        # consecutive decode dispatches chain without a host round-trip.
        # ``_carry_valid[i]`` False means host state is authoritative for
        # slot i (fresh admission / extend handoff / preemption / release
        # since the last dispatch); the merge happens outside jit, so the
        # decode program's signature — and the jit budget — is unchanged.
        self._carry = None
        self._carry_valid = np.zeros((sc.max_batch,), bool)
        #: dispatch stamping for the Slot.inflight protocol (see
        #: InflightStep.seq)
        self._dispatch_seq = 0
        self._slot_dispatch = [-1] * sc.max_batch
        #: conservative per-slot upper bound on the next write position
        #: while a dispatch is in flight (drives page ensure() when the
        #: true position is still on device)
        self._pos_ub = [0] * sc.max_batch

        # Bit-exact datapath predicate: is a decode-path forward bitwise
        # identical to the prefill-path forward for the same token at the
        # same position?  True for float GQA with the exact softmax on the
        # jnp reference path — prefill's attention_ref and decode's
        # gather-view attend are then the same f32 math.  False for MLA
        # (~1 ulp: different einsum orders when re-materializing K/V from
        # the latent), int8 KV (prefill attends float K/V, decode attends
        # dequantized codes), and LUT softmax (decode uses exact softmax).
        # The scheduler gates prefill-skip, preemption-resume, and chunked
        # prefill on this capability so token streams stay bit-identical
        # to dense.  The identity holds on the CPU backend only: on a TPU
        # the one-token decode attention rounds differently from prefill's
        # (a resumed seeded stream on minicpm-2b drew another token), while
        # the extend program matches prefill bit for bit, so there every
        # datapath replays through extend.
        self.bit_exact = (
            cfg.attn_kind == "gqa"
            and not self.quant_cache
            and self.kernel.get("softmax_mode", "safe") == "safe"
            and not self.kernel.get("use_pallas", False)
            and jax.default_backend() == "cpu"
        )

        # right-padding the prompt is only sound when the cache is
        # position-addressed and decode masks by position: true for dense
        # GQA / MLA caches, false for SSM/hybrid state and for rolling
        # sliding-window buffers (padding would evict real tokens).
        self.bucketable = self.cache_mgr.position_addressed
        # a bucket longer than the cache could not be inserted; drop those
        self.buckets = (
            tuple(b for b in sc.resolved_buckets() if b <= sc.max_seq_len)
            if self.bucketable
            else ()
        )

        # Cache-extending prefill program: ONE extra jitted program at a
        # fixed (max_batch, extend_width) shape.  Replayed tokens go
        # through the prefill-path forward against the populated caches,
        # which is what lets the scheduler plan prefill-skip / chunked /
        # preemption-resume admissions on datapaths where the decode
        # scan is NOT bitwise the prefill.  The window attend mirrors
        # the jnp reference path, so engines on the Pallas kernel keep
        # the legacy bit-exact gating (their prefill math is the
        # streaming kernel, not the reference).
        self.extend_width = (
            (sc.prefill_chunk or max(self.buckets)) if self.buckets else 0
        )
        self.cache_extend = bool(
            sc.cache_extend
            and self.bucketable
            and self.extend_width > 0
            and not self.kernel.get("use_pallas", False)
        )
        rep, csh = self._rep_sh, self._cache_out_sh
        self._decode_fn = jax.jit(
            self._decode_scan,
            out_shardings=(rep, rep, rep, rep, rep, rep, csh),
        ) if self.mesh is not None else jax.jit(self._decode_scan)
        self._prefill_fn: dict[int, Any] = {}  # jit cache per bucket length
        if not self.cache_extend:
            self._extend_fn = None
        elif self.mesh is not None:
            self._extend_fn = jax.jit(
                self._extend_batch, out_shardings=(rep, csh)
            )
        else:
            self._extend_fn = jax.jit(self._extend_batch)
        # Step-phase tracer (serve/phases.py), assigned by the Engine when
        # ServeConfig.trace_phases is on.  The default NULL_TRACER is a
        # shared no-op whose fence() never touches the device, so the
        # untraced hot loop is byte-for-byte the historical one.
        self.tracer = NULL_TRACER
        self.tel = {
            "tokens_generated": 0,
            "prefill_compiles": 0,
            "prefill_dispatches": 0,
            "decode_compiles": 0,
            "extend_compiles": 0,
            "extend_dispatches": 0,
            "prefill_time_s": 0.0,
            "decode_time_s": 0.0,
            "extend_time_s": 0.0,
            "draft_tokens_proposed": 0,
            "draft_tokens_accepted": 0,
            "spec_dispatches": 0,
            "spec_time_s": 0.0,
            "steps": 0,
        }

        # Speculative decoding: a draft model proposes spec_tokens greedy
        # tokens per resident decoding slot; the target verifies the whole
        # window in ONE cache-extending dispatch and accepts the longest
        # matching prefix plus a correction token.  The verify path IS the
        # extend program (no new target program — the len(buckets)+2
        # budget holds); rejected draft tokens rewind through the same
        # position-idempotent window-write machinery that extend replay
        # uses, which is why the feature is gated to cache_extend
        # datapaths.  The draft worker owns its own bounded program set
        # (at most len(buckets) draft prefills + 1 propose scan).
        self.draft: DraftWorker | None = None
        self.spec_k = 0
        if sc.speculative and not self.cache_extend:
            warnings.warn(
                "speculative decoding disabled: it verifies drafts through "
                "the cache-extending prefill program, which this datapath "
                "does not support (cache_extend off, unbucketable cache, "
                "or the Pallas prefill kernel)",
                RuntimeWarning,
                stacklevel=3,
            )
        elif sc.speculative:
            dcfg, dparams = draft if draft is not None else (
                self.cfg, self.params
            )
            if dcfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    "draft model must share the target vocabulary: "
                    f"draft {dcfg.vocab_size} vs target {cfg.vocab_size}"
                )
            self.spec_k = max(1, min(int(sc.spec_tokens), self.extend_width))
            self.draft = DraftWorker(
                dcfg, dparams, sc, self.buckets, self.spec_k
            )

    # ------------------------------------------------------------- view --
    @property
    def caps(self) -> ExecutorCaps:
        """Capabilities schedulers plan against (policy never inspects
        device state directly)."""
        return ExecutorCaps(
            max_batch=self.serve_cfg.max_batch,
            max_seq_len=self.serve_cfg.max_seq_len,
            decode_steps=self.serve_cfg.decode_steps,
            buckets=self.buckets,
            bucketable=self.bucketable,
            paged=self.kv_layout == "paged",
            bit_exact=self.bit_exact,
            prefix_cache=self.cache_mgr.prefix_cache,
            cache_extend=self.cache_extend,
        )

    def kv_stats(self) -> dict:
        """Current KV-cache occupancy (layout, bytes, page utilization)."""
        return self.cache_mgr.stats().as_dict()

    # ------------------------------------------------------------ device --
    def _prefill_batch(self, params, tokens, lengths, caches, slots,
                       shared=None):
        """Prefill up to ``max_batch`` same-bucket prompts in ONE dispatch.

        ``tokens``: (max_batch, bucket) int32, right-padded per row.
        ``lengths``: (max_batch,) true prompt lengths (0 for pad rows).
        ``slots``: (max_batch,) destination slot per row; the value
        ``max_batch`` marks a pad row (dropped by the dense scatter,
        routed to the trash page by the paged scatter).
        ``shared``: (max_batch,) leading prefix-cache pages per row whose
        recomputed values must not touch shared storage (their insert
        columns scatter to the trash page; 0 everywhere when the prefix
        cache is off).
        All four are traced, so every same-bucket wave reuses one
        compiled program.  Returns (per-row last-token logits (N, V),
        updated caches).
        """
        cfg = self.cfg
        nb, bucket = tokens.shape
        mask = jnp.arange(bucket, dtype=jnp.int32)[None, :] < lengths[:, None]
        tokens = jnp.where(mask, tokens, 0)  # canonical pad id
        # the model writes its natural contiguous (dense) scratch cache;
        # insert_prefill is the only layout-specific step.  Paged: the
        # scratch only needs to cover the bucket (rounded up to whole
        # pages), so the transient footprint scales with the bucket, not
        # with max_batch x max_seq_len.  Dense keeps the full-length
        # scratch: its insert scatters whole slot slabs (bit-identical
        # historical behavior, zeroed tail included).
        if self.kv_layout == "paged":
            ps = self.cache_mgr.page_size
            scratch_len = -(-bucket // ps) * ps
        else:
            scratch_len = self.serve_cfg.max_seq_len
        small = kv_cache.init_caches(
            cfg, nb, scratch_len,
            dtype=jnp.float32, quantized=self.quant_cache,
        )
        logits, filled, _ = lm.forward(
            params, cfg, {"tokens": tokens}, mode="prefill",
            caches=small, kernel=self.kernel,
        )
        # causal attention keeps positions < length independent of the pad
        # tail; each row's true logits live at index length-1
        idx = jnp.maximum(lengths - 1, 0)[:, None, None]
        last = jnp.take_along_axis(logits, idx, axis=1)[:, 0]
        filled = kv_cache.mask_cache_tail(filled, lengths)
        new_caches = self.cache_mgr.insert_prefill(
            caches, filled, slots, shared
        )
        return last, new_caches

    def _extend_batch(self, params, tokens, win_len, starts, caches):
        """Extend resident slots' caches by one token window each in ONE
        fixed-shape dispatch (the cache-extending prefill program).

        ``tokens``: (max_batch, extend_width) int32, right-padded per
        row.  ``win_len``: (max_batch,) valid tokens per row (0 = idle
        row).  ``starts``: (max_batch,) each row's first write position.
        Row i is slot i — the same full-batch convention as the decode
        scan, so no slot gather is needed.  The forward runs in
        ``extend`` mode: window tokens are written at global positions
        ``starts + [0, W)`` through the dense/paged scatter and attended
        with prefill-path math against history + window, making the new
        cache entries and logits bitwise what a whole-prompt prefill
        would have produced at those positions.  Masked entries carry
        the ``max_seq_len`` sentinel position (dropped / trash-paged).
        Returns (full per-window logits (max_batch, W, V), updated
        caches) — tail replay selects each row's last valid position
        eagerly on host, while speculative verification consumes every
        window position's logits, so ONE program serves both.
        """
        cfg = self.cfg
        nb, w = tokens.shape
        mask = jnp.arange(w, dtype=jnp.int32)[None, :] < win_len[:, None]
        tokens = jnp.where(mask, tokens, 0)  # canonical pad id
        positions = starts[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]
        positions = jnp.where(mask, positions, self.serve_cfg.max_seq_len)
        logits, new_caches, _ = lm.forward(
            params, cfg, {"tokens": tokens}, mode="extend",
            caches=caches, positions=positions, kernel=self.kernel,
        )
        return logits, new_caches

    def _decode_scan(self, params, tokens, positions, active, rem, eos,
                     temp, top_k, top_p, seed, forced, n_forced, caches,
                     key):
        """Run ``decode_steps`` fused decode steps under one dispatch.

        All arrays are per-slot (B,): ``tokens`` last sampled token,
        ``positions`` next write position, ``active`` live mask, ``rem``
        generation budget left, ``eos`` per-request eos id (-1 = none).
        ``temp``/``top_k``/``top_p``/``seed`` are the stacked per-request
        sampling knobs (scheduler-stamped sentinels when absent — see
        ``encode_sampling``); they ride the dispatch exactly like ``eos``
        so a mixed greedy/sampled batch shares this one program.
        Inactive slots freeze (token, position); re-running a frozen
        position is idempotent for position-addressed caches (dense slabs
        and pages alike — retired paged slots write the trash page) and
        harmless for retired SSM slots (their state is overwritten on
        re-prefill).

        ``forced``: (decode_steps, B) teacher-forced next tokens,
        ``n_forced``: (B,) how many leading steps of this dispatch force
        each slot (prefix-cache prefill-skip and chunked prefill: the
        unprefilled prompt tail rides the decode program).  A forced step
        writes its prompt token's KV, overrides the sampled next token,
        emits nothing, and leaves the generation budget and eos/budget
        deactivation alone — so the first *sampled* token after the tail
        sees logits bitwise equal to the prefill path's last-position
        logits.  All zeros when nothing is forced, which reduces to the
        historical behavior.
        Returns (per-step next tokens, per-step emit mask, final carry
        token, final positions, final active mask, final budget,
        caches).
        """
        sc = self.serve_cfg
        keys = jax.random.split(key, sc.decode_steps)
        flags = (
            jnp.arange(sc.decode_steps, dtype=jnp.int32)[:, None]
            < n_forced[None, :]
        )  # (T, B)

        def body(carry, xs):
            k, forced_t, flag_t = xs
            tok, pos, act, budget, c = carry
            logits, new_c, _ = lm.forward(
                params, self.cfg, {"tokens": tok[:, None]}, mode="decode",
                caches=c, positions=pos, kernel=self.kernel,
            )
            sampled = sample_tokens(
                logits[:, -1], k, temperature=temp, top_k=top_k,
                top_p=top_p, seed=seed, positions=pos,
            )
            nxt = jnp.where(act, jnp.where(flag_t, forced_t, sampled), tok)
            emit = act & ~flag_t
            emitted = (nxt, emit)
            budget = jnp.where(emit, budget - 1, budget)
            new_pos = jnp.where(act, pos + 1, pos)
            new_act = (
                act
                & (flag_t | ((nxt != eos) & (budget > 0)))
                & (new_pos + 1 < sc.max_seq_len)
            )
            return (nxt, new_pos, new_act, budget, new_c), emitted

        init = (tokens, positions, active, rem, caches)
        (tok, pos, act, rem, caches), (toks_t, emit_t) = jax.lax.scan(
            body, init, (keys, forced, flags)
        )
        # the final budget rides along so the async loop's device carry
        # can chain dispatches without reading ``generated`` on host
        return toks_t, emit_t, tok, pos, act, rem, caches

    # ----------------------------------------------------------- execute --
    def execute(self, decision: ScheduleDecision) -> StepOutput:
        """Apply one :class:`ScheduleDecision` synchronously:
        :meth:`dispatch` then :meth:`collect`, back to back.  The legacy
        engine loop — byte-identical op order to the historical
        monolithic ``execute`` (the async loop interleaves the two
        halves across steps instead)."""
        return self.collect(self.dispatch(decision))

    def dispatch(self, decision: ScheduleDecision) -> InflightStep:
        """The non-blocking half: reset preempted slots, activate
        admissions (prefix-skip slots immediately, prefill / chunked
        slots through their bucket dispatches), drain cache-extend
        windows, then *enqueue* the decode scan and return without
        waiting for it.  Prefill and extend stay internally synchronous
        (their first tokens must be sampled host-side either way); the
        decode scan — the steady-state hot path — is what pipelines.
        The scheduler already performed the host-side page bookkeeping;
        nothing here chooses anything."""
        tel = self.tel
        tel["steps"] += 1
        out = StepOutput(stats={"prefilled": 0, "decoded": 0})
        for idx, req in decision.preempted:
            # pages were freed by the scheduler; drop the execution state
            self.slots[idx] = Slot()
            self._host_dirty(idx)
            out.preempted.append(req)
        for adm in decision.admissions:
            slot = self.slots[adm.slot]
            slot.admit_seq = adm.admit_seq
            slot.admit_gen = adm.admit_gen
            if adm.mode in (MODE_SKIP, MODE_FORK):
                # MODE_SKIP: the shared pages hold every position <
                # write_from; no prompt-prefill dispatch at all — the
                # remaining tail replays per the admission's split.
                # MODE_FORK is mechanically identical: the n-best child
                # entered owning refcounted views of its parent's pages
                # (prompt AND generated-into), so only the prompt's last
                # token replays; the child's first diverging write
                # copy-on-writes it off the shared last page
                slot.active, slot.request = True, adm.request
                slot.pos = adm.write_from
                self._activate_tail(slot, adm, adm.write_from)
                self._host_dirty(adm.slot)
                out.stats["prefilled"] += 1
        for bucket, group in decision.prefill_groups.items():
            self._dispatch_prefill(bucket, group, out)
        self._dispatch_extend(decision, out)
        spec_served = self._dispatch_speculative(decision, out)
        return self._dispatch_decode(decision, out, exclude=spec_served)

    def collect(self, inflight: InflightStep) -> StepOutput:
        """The blocking half: transfer the decode scan's results to host
        (the only point the loop waits on the device), route emitted
        tokens into each slot's request, update slot execution state
        from the device carry, and retire finished slots.  Under the
        async loop this runs one step late — while the *next* decision's
        dispatch is already in flight — so each slot is re-checked
        against the dispatch-time ``snapshot``: a cancelled or
        turned-over slot's in-flight tokens are discarded, never routed
        to a dead stream."""
        out = inflight.out
        if inflight.dev is None:
            return out
        tel, tr = self.tel, self.tracer
        decision = inflight.decision
        tr.collect_begin(inflight.t_dispatch)
        with tr.phase(tr.collect_phase):
            toks_t, emit_t, tok_f, pos_f, act_f, rem_f = (
                np.asarray(x) for x in inflight.dev
            )
        tel["decode_time_s"] += time.perf_counter() - inflight.t0
        with tr.phase("sample"):
            for idx in inflight.decode_set:
                slot = self.slots[idx]
                req = inflight.snapshot.get(idx)
                if (
                    req is None
                    or req.cancelled
                    or not slot.active
                    or slot.request is not req
                    or slot.admit_seq != inflight.admit_seqs.get(idx, -2)
                ):
                    # mid-flight cancel, preemption, or slot turnover
                    # (including the same request re-admitted into the
                    # same slot — the admit_seq stamp): the tokens this
                    # dispatch produced for the slot are dropped at the
                    # one-step-stale boundary; pages were already freed
                    # by release()/preempt, and a preempted request
                    # regenerates the discarded tokens after resume
                    continue
                for t in range(toks_t.shape[0]):
                    if not emit_t[t, idx]:
                        continue
                    req.generated.append(int(toks_t[t, idx]))
                    out.stats["decoded"] += 1
                    tel["tokens_generated"] += 1
                    out.tokens.append((
                        req.uid, int(toks_t[t, idx]),
                        len(req.generated) - 1,
                    ))
                slot.pos = int(pos_f[idx])
                slot.last_token = int(tok_f[idx])
                if decision.register_decoded:
                    # decode-completed full pages become shareable too:
                    # their content is bit-exact with a prefill of the
                    # same tokens on this datapath
                    self.cache_mgr.register_filled(
                        idx, req.resume_tokens, slot.pos
                    )
                if not act_f[idx]:
                    out.finished.append(req)
                    self.slots[idx] = Slot()
                    self.cache_mgr.free(idx)
                    self._host_dirty(idx)
                else:
                    self._retire(idx, out)
        # clear in-flight marks (also for skipped/cancelled slots) —
        # unless a newer dispatch already re-marked the slot (the async
        # loop dispatches N+1 before collecting N, often over the same
        # slots); the marks tell policies which residents have an
        # uncollected dispatch (preempting one discards its tokens)
        for idx in inflight.decode_set:
            if self._slot_dispatch[idx] == inflight.seq:
                self.slots[idx].inflight = False
        return out

    def _activate_tail(self, slot: Slot, adm: Admission, start: int) -> None:
        """Split an admission's unwritten token tail per its
        ``decode_from`` stamp: positions in [start, decode_from) replay
        through the cache-extending prefill program, positions from
        ``decode_from`` on teacher-force through the decode scan.  With
        ``decode_from == start`` (the bit-exact datapaths' plan) the
        whole tail rides the decode scan and the carry token is primed
        immediately — the historical behavior, byte for byte."""
        tail = list(adm.tokens[start:adm.decode_from])
        pend = list(adm.tokens[adm.decode_from:])
        if tail:
            slot.prefill_tail = tail
            slot.pending = pend
        else:
            slot.last_token = pend[0]
            slot.pending = pend[1:]

    def release(self, idx: int) -> None:
        """Immediately free a resident slot's pages and execution state
        (request cancellation); safe on inactive slots.  An in-flight
        dispatch covering the slot keeps writing through its captured
        page table — device program order guarantees those writes land
        before any later dispatch reuses the freed pages, and the next
        table sync points the row at the trash page."""
        self.cache_mgr.free(idx)
        self.slots[idx] = Slot()
        self._host_dirty(idx)

    def _host_dirty(self, idx: int) -> None:
        """Mark host slot state authoritative for ``idx``: the device
        carry must not override it at the next decode dispatch (fresh
        admission, extend handoff, preemption, release, retire).  The
        same turnovers invalidate the slot's draft-cache sync stamp: the
        draft worker re-prefills the row from its token history before
        speculating for it again."""
        self._carry_valid[idx] = False
        self._pos_ub[idx] = self.slots[idx].pos
        if self.draft is not None:
            self.draft.pos[idx] = -1

    def _reserve_cap(self, req: Request) -> int:
        """The admission-time worst-case length reservation for ``req``
        (scheduler ``_reserve_len``): the hard cap for conservative page
        ``ensure`` while the true position is still on device."""
        return min(
            len(req.prompt) + req.max_new_tokens, self.serve_cfg.max_seq_len
        )

    def _dispatch_prefill(
        self, bucket: int, group: list[Admission], out: StepOutput
    ):
        """One fixed-shape prefill dispatch filling every slot in ``group``
        (all rows share ``bucket``); pad rows carry the slot sentinel
        ``max_batch`` so their writes are dropped.  Each row's dispatched
        tokens are its effective prompt (original prompt + generated-so-far
        for a preempted request being resumed) truncated to ``fill_len``
        (the whole prompt for MODE_PREFILL, the first chunk for
        MODE_CHUNKED) and ``shared_pages`` its count of prefix-cache pages
        the insert must not overwrite.  Only MODE_PREFILL rows sample a
        first token from the dispatch's last-position logits; a chunk's
        logits predict a prompt token the request already has, so chunked
        rows activate with their teacher-forced tail instead."""
        sc, tel, tr = self.serve_cfg, self.tel, self.tracer
        nb = sc.max_batch
        with tr.phase("host_prep"):
            toks = np.zeros((nb, bucket), np.int32)
            lengths = np.zeros((nb,), np.int32)
            slots_arr = np.full((nb,), nb, np.int32)
            shared_arr = np.zeros((nb,), np.int32)
            for row, adm in enumerate(group):
                n = adm.fill_len
                toks[row, :n] = adm.tokens[:n]
                lengths[row] = n
                slots_arr[row] = adm.slot
                shared_arr[row] = adm.shared_pages
            # victim-tier movement queued by this step's admissions must
            # land before the prefill program runs: spills drain the
            # rows of pages the scatter is about to overwrite, swap-ins
            # fill the covered columns the scatter redirects to trash
            self.caches = self.cache_mgr.flush_swaps(self.caches)
            self.caches = self.cache_mgr.write_table(self.caches)
        fn = self._prefill_fn.get(bucket)
        if fn is None:
            if self.mesh is not None:
                fn = jax.jit(
                    self._prefill_batch,
                    out_shardings=(self._rep_sh, self._cache_out_sh),
                )
            else:
                fn = jax.jit(self._prefill_batch)
            self._prefill_fn[bucket] = fn
            tel["prefill_compiles"] += 1
        t0 = time.perf_counter()
        with tr.phase("dispatch"):
            last, self.caches = fn(
                self.params, jnp.asarray(toks), jnp.asarray(lengths),
                self.caches, jnp.asarray(slots_arr), jnp.asarray(shared_arr),
            )
        with tr.phase("device"):
            tr.fence((last, self.caches))
        tel["prefill_dispatches"] += 1
        # one vectorized sample + one device->host transfer for the group;
        # the admission carries the scheduler-stamped per-request knobs
        self.key, sub = jax.random.split(self.key)
        with tr.phase("sample"):
            knobs = [adm.sampling for adm in group]
            first_tokens = np.asarray(
                sample_tokens(
                    last[:len(group)], sub,
                    temperature=jnp.asarray(
                        [s[0] for s in knobs], jnp.float32
                    ),
                    top_k=jnp.asarray([s[1] for s in knobs], jnp.int32),
                    top_p=jnp.asarray([s[2] for s in knobs], jnp.float32),
                    seed=jnp.asarray([s[3] for s in knobs], jnp.int32),
                    positions=jnp.asarray(
                        [len(adm.tokens) - 1 for adm in group], jnp.int32
                    ),
                )
            )
            for row, adm in enumerate(group):
                slot = self.slots[adm.slot]
                slot.active, slot.request = True, adm.request
                self._host_dirty(adm.slot)
                if adm.emits_first_token:
                    nxt = int(first_tokens[row])
                    adm.request.generated.append(nxt)
                    tel["tokens_generated"] += 1
                    out.tokens.append(
                        (adm.request.uid, nxt, len(adm.request.generated) - 1)
                    )
                    slot.pos = len(adm.tokens)  # next write position
                    slot.last_token = nxt
                else:  # MODE_CHUNKED: tail replays per the admission split
                    slot.pos = adm.fill_len
                    self._activate_tail(slot, adm, adm.fill_len)
                out.stats["prefilled"] += 1
                self._retire(adm.slot, out)
        tel["prefill_time_s"] += time.perf_counter() - t0

    def _dispatch_extend(self, decision: ScheduleDecision, out: StepOutput):
        """ONE fixed-shape dispatch draining every listed slot's prefill
        tail by up to ``extend_width`` tokens through the cache-extending
        prefill program.  A slot whose tail fully drains either hands off
        to its teacher-forced pending (preemption resume: the generated
        part replays through the decode math that originally wrote it) or
        samples its first token from the window's last-position logits —
        exactly the logits a whole-prompt prefill would have produced."""
        work = [
            i for i in decision.extend_slots
            if self.slots[i].active and self.slots[i].prefill_tail
        ]
        if not work:
            return
        sc, tel, tr = self.serve_cfg, self.tel, self.tracer
        nb, w = sc.max_batch, self.extend_width
        with tr.phase("host_prep"):
            toks = np.zeros((nb, w), np.int32)
            lens = np.zeros((nb,), np.int32)
            starts = np.zeros((nb,), np.int32)
            for i in work:
                slot = self.slots[i]
                n = min(len(slot.prefill_tail), w)
                toks[i, :n] = slot.prefill_tail[:n]
                lens[i] = n
                starts[i] = slot.pos
                # grow pages over the write range; shared pages
                # overlapping it are copy-on-write replaced pre-scatter
                self.cache_mgr.ensure(i, slot.pos + n, write_from=slot.pos)
            # swaps before CoW copies: a CoW destination can be a
            # just-evicted page whose rows must spill first
            self.caches = self.cache_mgr.flush_swaps(self.caches)
            self.caches = self.cache_mgr.flush_copies(self.caches)
            self.caches = self.cache_mgr.write_table(self.caches)
        if tel["extend_compiles"] == 0:
            tel["extend_compiles"] = 1  # one program, fixed shapes
        t0 = time.perf_counter()
        with tr.phase("dispatch"):
            logits, self.caches = self._extend_fn(
                self.params, jnp.asarray(toks), jnp.asarray(lens),
                jnp.asarray(starts), self.caches,
            )
        with tr.phase("device"):
            tr.fence((logits, self.caches))
        tel["extend_dispatches"] += 1
        self.key, sub = jax.random.split(self.key)
        with tr.phase("sample"):
            # each row's true logits live at its window's last valid
            # position (selected eagerly — the program returns the full
            # window so speculative verification can reuse it)
            idx = np.maximum(lens - 1, 0)
            last = jnp.take_along_axis(
                logits, jnp.asarray(idx)[:, None, None], axis=1
            )[:, 0]
            knobs = [
                encode_sampling(
                    self.slots[i].request if i in work else None,
                    sc.temperature,
                )
                for i in range(nb)
            ]
            first_tokens = np.asarray(
                sample_tokens(
                    last, sub,
                    temperature=jnp.asarray(
                        [s[0] for s in knobs], jnp.float32
                    ),
                    top_k=jnp.asarray([s[1] for s in knobs], jnp.int32),
                    top_p=jnp.asarray([s[2] for s in knobs], jnp.float32),
                    seed=jnp.asarray([s[3] for s in knobs], jnp.int32),
                    positions=jnp.asarray(starts + idx, jnp.int32),
                )
            )
            for i in work:
                slot = self.slots[i]
                n = int(lens[i])
                del slot.prefill_tail[:n]
                slot.pos += n
                self._host_dirty(i)
                if slot.prefill_tail:
                    continue  # another window next step
                if slot.pending:
                    # resume handoff: the generated part teacher-forces
                    # through the decode scan from here
                    slot.last_token = slot.pending.pop(0)
                else:
                    nxt = int(first_tokens[i])
                    slot.request.generated.append(nxt)
                    tel["tokens_generated"] += 1
                    out.tokens.append(
                        (slot.request.uid, nxt,
                         len(slot.request.generated) - 1)
                    )
                    slot.last_token = nxt
                # window-written full pages hold prefill-path content —
                # as shareable as a bucket dispatch's, on every datapath
                self.cache_mgr.register_filled(
                    i, slot.request.resume_tokens, slot.pos
                )
                self._retire(i, out)
        tel["extend_time_s"] += time.perf_counter() - t0

    def _dispatch_speculative(
        self, decision: ScheduleDecision, out: StepOutput
    ) -> set[int]:
        """Advance eligible decode slots by up to ``spec_k + 1`` tokens in
        one draft-propose + one target-verify dispatch; returns the slots
        served (the decode scan skips them this step).

        The draft greedily proposes ``spec_k`` tokens per slot.  The
        target verifies the whole window [carry, d1..d_{k-1}] through the
        cache-extending prefill program at starts = pos: the window's
        logits at offset j are exactly what the decode scan would have
        produced for position pos+j, so sampling them with the same
        per-request knobs and position-keyed PRNG yields the target's own
        token s_j.  The accepted prefix is the run of j with s_j == d_j;
        one correction token (the target's sample at the first mismatch)
        always ships, so a fully-rejected draft still nets one token —
        greedy speculative output is bitwise the non-speculative stream
        on bit-exact datapaths (test-enforced).  Rejected window
        positions hold stale KV that the next window/decode write
        overwrites — the same position-idempotence extend replay relies
        on, which is why speculation is gated to cache_extend datapaths.

        Host emission replicates the decode scan's deactivation rules
        exactly: emit eos then stop, stop at budget zero, stop when the
        next write position would reach max_seq_len.  Served slots are
        marked host-dirty (the async carry never covers them), then the
        draft sync stamp is advanced — the accepted prefix was written to
        the draft cache during proposal, so steady-state speculation
        needs no draft resync at all.
        """
        if self.draft is None:
            return set()
        sc, tel, tr = self.serve_cfg, self.tel, self.tracer
        k, nb = self.spec_k, sc.max_batch
        cand: list[int] = []
        for i in sorted(set(decision.decode_slots)):
            slot = self.slots[i]
            if not slot.active or slot.prefill_tail or slot.pending:
                continue
            if slot.request.cancelled:
                continue
            if self.async_loop and self._carry_valid[i]:
                continue  # the device carry owns this slot's truth
            if slot.request.max_new_tokens <= len(slot.request.generated):
                continue
            if slot.pos + k > sc.max_seq_len - 1:
                continue  # near the cap: plain decode finishes it
            cand.append(i)
        if not cand:
            return set()
        t0 = time.perf_counter()
        with tr.phase("host_prep"):
            # resync draft cache rows whose (pos, carry) drifted from the
            # target's — any admission/extend/preempt/release turnover
            # invalidated them via _host_dirty
            need: list[tuple[int, list[int]]] = []
            fit: list[int] = []
            for i in cand:
                slot = self.slots[i]
                if (
                    self.draft.pos[i] == slot.pos
                    and self.draft.tok[i] == slot.last_token
                ):
                    fit.append(i)
                    continue
                hist = list(slot.request.resume_tokens[: slot.pos])
                if hist and self.draft.bucket_for(len(hist)) is None:
                    continue  # history outgrew the draft buckets
                need.append((i, hist))
                fit.append(i)
            cand = fit
            if not cand:
                tel["spec_time_s"] += time.perf_counter() - t0
                return set()
            self.draft.sync(need, tel)
            for i, _ in need:
                self.draft.pos[i] = self.slots[i].pos
                self.draft.tok[i] = self.slots[i].last_token
            # propose: one draft scan over the full batch
            d_tok = np.zeros((nb,), np.int32)
            d_pos = np.zeros((nb,), np.int32)
            d_act = np.zeros((nb,), bool)
            for i in cand:
                d_tok[i] = self.slots[i].last_token
                d_pos[i] = self.slots[i].pos
                d_act[i] = True
        with tr.phase("dispatch"):
            toks_t, self.draft.caches = self.draft._propose_fn(
                self.draft.params, jnp.asarray(d_tok), jnp.asarray(d_pos),
                jnp.asarray(d_act), self.draft.caches,
            )
        props = np.asarray(toks_t)  # (k, nb)
        with tr.phase("host_prep"):
            # verify: ONE extend dispatch over [carry, d1..d_{k-1}]
            vt = np.zeros((nb, self.extend_width), np.int32)
            vl = np.zeros((nb,), np.int32)
            vs = np.zeros((nb,), np.int32)
            for i in cand:
                slot = self.slots[i]
                vt[i, 0] = slot.last_token
                vt[i, 1:k] = props[: k - 1, i]
                vl[i] = k
                vs[i] = slot.pos
                self.cache_mgr.ensure(i, slot.pos + k, write_from=slot.pos)
            self.caches = self.cache_mgr.flush_swaps(self.caches)
            self.caches = self.cache_mgr.flush_copies(self.caches)
            self.caches = self.cache_mgr.write_table(self.caches)
        with tr.phase("dispatch"):
            logits, self.caches = self._extend_fn(
                self.params, jnp.asarray(vt), jnp.asarray(vl),
                jnp.asarray(vs), self.caches,
            )
        with tr.phase("device"):
            tr.fence((logits, self.caches))
        tel["spec_dispatches"] += 1
        self.key, sub = jax.random.split(self.key)
        with tr.phase("sample"):
            knobs = [
                encode_sampling(
                    self.slots[i].request if i in cand else None,
                    sc.temperature,
                )
                for i in range(nb)
            ]
            temp = jnp.asarray([s[0] for s in knobs], jnp.float32)
            top_k = jnp.asarray([s[1] for s in knobs], jnp.int32)
            top_p = jnp.asarray([s[2] for s in knobs], jnp.float32)
            seedv = jnp.asarray([s[3] for s in knobs], jnp.int32)
            samp = np.stack([
                np.asarray(
                    sample_tokens(
                        logits[:, t], jax.random.fold_in(sub, t),
                        temperature=temp, top_k=top_k, top_p=top_p,
                        seed=seedv,
                        positions=jnp.asarray(d_pos + t, jnp.int32),
                    )
                )
                for t in range(k)
            ])  # (k, nb): the target's own token at each window offset
            served: set[int] = set()
            for i in cand:
                slot = self.slots[i]
                req = slot.request
                d = [int(props[t, i]) for t in range(k)]
                s = [int(samp[t, i]) for t in range(k)]
                m = 0
                while m < k and s[m] == d[m]:
                    m += 1
                emitted = d[:m] + ([] if m == k else [s[m]])
                req.draft_proposed += k
                req.draft_accepted += m
                tel["draft_tokens_proposed"] += k
                tel["draft_tokens_accepted"] += m
                base = slot.pos
                n_emit = 0
                for nxt in emitted:
                    req.generated.append(nxt)
                    out.stats["decoded"] += 1
                    tel["tokens_generated"] += 1
                    out.tokens.append((req.uid, nxt, len(req.generated) - 1))
                    n_emit += 1
                    if (
                        (req.eos_id is not None and nxt == req.eos_id)
                        or len(req.generated) >= req.max_new_tokens
                        or base + n_emit + 1 >= sc.max_seq_len
                    ):
                        break
                slot.pos = base + n_emit
                slot.last_token = emitted[n_emit - 1]
                self._host_dirty(i)
                # accepted positions were written to the draft cache
                # during proposal, so the draft is synced by construction
                self.draft.pos[i] = slot.pos
                self.draft.tok[i] = slot.last_token
                self.cache_mgr.register_filled(
                    i, req.resume_tokens, slot.pos
                )
                self._retire(i, out)
                served.add(i)
        tel["spec_time_s"] += time.perf_counter() - t0
        return served

    def _dispatch_decode(
        self,
        decision: ScheduleDecision,
        out: StepOutput,
        exclude: frozenset[int] | set[int] = frozenset(),
    ) -> InflightStep:
        """Enqueue the decode scan for the decision's decode slots
        (per-slot active masks; slots outside the decision freeze for
        this dispatch; a slot still draining a prefill tail is not ready
        to decode) and return the :class:`InflightStep` without waiting.

        Synchronous mode builds every scan input from host slot state —
        the historical op order, byte for byte.  Async mode merges the
        device carry over the host arrays (outside jit: the program
        signature is unchanged) for slots whose last dispatch has not
        been collected yet, so consecutive decode dispatches chain
        entirely on device; page ``ensure`` then works on a conservative
        position upper bound (stale-low ``write_from``, +decode_steps
        upper), which can only over-cover the true write range — extra
        pages stay within the admission-time worst-case reservation."""
        sc, tel, tr = self.serve_cfg, self.tel, self.tracer
        decode_set = {
            i for i in decision.decode_slots
            if self.slots[i].active
            and not self.slots[i].prefill_tail
            and i not in exclude  # already advanced speculatively
        }
        if not decode_set:
            return InflightStep(out=out, decision=decision)
        nb = sc.max_batch
        use_carry = self.async_loop and self._carry is not None
        with tr.phase("host_prep"):
            forced = np.zeros((sc.decode_steps, nb), np.int32)
            n_forced = np.zeros((nb,), np.int32)
            for idx in sorted(decode_set):
                slot = self.slots[idx]
                nf = min(len(slot.pending), sc.decode_steps)
                if nf:
                    forced[:nf, idx] = slot.pending[:nf]
                    n_forced[idx] = nf
                    # consumed by THIS dispatch: trimming here (not at
                    # collect) keeps the next dispatch's forced window
                    # correct even before this one is collected
                    del slot.pending[:nf]
                if use_carry and self._carry_valid[idx]:
                    # true position still on device: ensure against the
                    # conservative upper bound; write_from = the stale
                    # host pos (a lower bound) so CoW covers the range
                    base = self._pos_ub[idx]
                    upto = min(
                        base + sc.decode_steps,
                        self._reserve_cap(slot.request),
                    )
                    self._pos_ub[idx] = upto
                    self.cache_mgr.ensure(idx, upto, write_from=slot.pos)
                    continue
                # the scan advances at most min(decode_steps, forced
                # tail + remaining budget) positions, so this never
                # outgrows the pages reserved at admission; passing
                # the write range lets the manager copy-on-write any
                # shared page before the dispatch scatters into it
                rem_i = max(
                    slot.request.max_new_tokens - len(slot.request.generated),
                    1,
                )
                self.cache_mgr.ensure(
                    idx,
                    min(slot.pos + min(sc.decode_steps, nf + rem_i),
                        sc.max_seq_len),
                    write_from=slot.pos,
                )
                if self.async_loop:
                    self._pos_ub[idx] = min(
                        slot.pos + min(sc.decode_steps, nf + rem_i),
                        sc.max_seq_len,
                    )
            self.caches = self.cache_mgr.flush_swaps(self.caches)
            self.caches = self.cache_mgr.flush_copies(self.caches)
            self.caches = self.cache_mgr.write_table(self.caches)
            tokens = np.asarray([s.last_token for s in self.slots], np.int32)
            positions = np.asarray(
                [s.pos if s.active else 0 for s in self.slots], np.int32
            )
            active = np.asarray(
                [
                    s.active and i in decode_set
                    for i, s in enumerate(self.slots)
                ],
                bool,
            )
            rem = np.asarray(
                [
                    max(s.request.max_new_tokens - len(s.request.generated), 0)
                    if s.active and i in decode_set
                    else 0
                    for i, s in enumerate(self.slots)
                ],
                np.int32,
            )
            eos = np.asarray(
                [
                    s.request.eos_id
                    if s.active and s.request.eos_id is not None
                    else -1
                    for s in self.slots
                ],
                np.int32,
            )
            # stacked per-request sampling knobs, built next to eos from
            # the same host slot state; a carried (uncollected) slot's
            # request cannot change mid-residency, so rebuilding from
            # host is sound under the async merge too
            knobs = [
                encode_sampling(
                    self.slots[i].request
                    if self.slots[i].active and i in decode_set
                    else None,
                    sc.temperature,
                )
                for i in range(nb)
            ]
            temp = np.asarray([s[0] for s in knobs], np.float32)
            top_k = np.asarray([s[1] for s in knobs], np.int32)
            top_p = np.asarray([s[2] for s in knobs], np.float32)
            seedv = np.asarray([s[3] for s in knobs], np.int32)
            if use_carry:
                # merge: device truth for uncollected slots, host truth
                # where an admission/extend/release made host fresh.
                # Plain (B,)-element ops outside jit — no new compiled
                # engine programs.
                # .copy() is load-bearing: device_put on the CPU backend
                # zero-copies an aligned numpy buffer, so handing the
                # live mask to jax would alias it — the asynchronously
                # dispatched merge could then read the ``[:] = True``
                # reset below (or a later ``_host_dirty``) instead of
                # the merge-time values, silently resurrecting a stale
                # device carry for a just-turned-over slot
                v = jnp.asarray(self._carry_valid.copy())
                c_tok, c_pos, c_act, c_rem = self._carry
                tok_in = jnp.where(v, c_tok, tokens)
                pos_in = jnp.where(v, c_pos, positions)
                act_in = jnp.asarray(active) & jnp.where(v, c_act, True)
                rem_in = jnp.where(v, c_rem, rem)
            else:
                tok_in, pos_in = jnp.asarray(tokens), jnp.asarray(positions)
                act_in, rem_in = jnp.asarray(active), jnp.asarray(rem)
            if self.mesh is not None:
                # commit the per-slot operands to one replicated placement
                # on every dispatch: an uncommitted host array (first
                # step) and a committed carry-merge result would
                # otherwise key two decode programs
                tok_in, pos_in, act_in, rem_in = (
                    jax.device_put(x, self._rep_sh)
                    for x in (tok_in, pos_in, act_in, rem_in)
                )
        self.key, sub = jax.random.split(self.key)
        if tel["decode_compiles"] == 0:
            tel["decode_compiles"] = 1  # one program, fixed shapes
        t0 = time.perf_counter()
        with tr.phase("dispatch"):
            toks_t, emit_t, tok_f, pos_f, act_f, rem_f, self.caches = (
                self._decode_fn(
                    self.params, tok_in, pos_in,
                    act_in, rem_in, jnp.asarray(eos),
                    jnp.asarray(temp), jnp.asarray(top_k),
                    jnp.asarray(top_p), jnp.asarray(seedv),
                    jnp.asarray(forced), jnp.asarray(n_forced),
                    self.caches, sub,
                )
            )
        with tr.phase("device"):
            tr.fence((toks_t, emit_t, tok_f, pos_f, act_f, self.caches))
        if self.async_loop:
            # every batch row's scan output reflects the merged (device
            # or fresh-host) input, so the whole carry is valid until
            # the next host-side slot mutation
            self._carry = (tok_f, pos_f, act_f, rem_f)
            self._carry_valid[:] = True
        snapshot = {i: self.slots[i].request for i in decode_set}
        admit_seqs = {i: self.slots[i].admit_seq for i in decode_set}
        self._dispatch_seq += 1
        for i in decode_set:
            self.slots[i].inflight = True
            self._slot_dispatch[i] = self._dispatch_seq
        return InflightStep(
            out=out, decision=decision, decode_set=tuple(sorted(decode_set)),
            dev=(toks_t, emit_t, tok_f, pos_f, act_f, rem_f),
            snapshot=snapshot, admit_seqs=admit_seqs, n_forced=n_forced,
            seq=self._dispatch_seq, t_dispatch=tr.mark_dispatch(), t0=t0,
        )

    def _retire(self, idx: int, out: StepOutput):
        slot = self.slots[idx]
        if slot.active and (
            slot.request.done or slot.pos + 1 >= self.serve_cfg.max_seq_len
        ):
            out.finished.append(slot.request)
            self._finish_slot(idx)

    def _finish_slot(self, idx: int):
        self.slots[idx] = Slot()
        self.cache_mgr.free(idx)
        self._host_dirty(idx)
