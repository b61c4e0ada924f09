"""Cache-extending prefill program: the parity matrix that retires the
silent quantized-datapath fallbacks.

Chunked prefill, prefix-cache prefill-skip, and preemption-resume used
to be gated on ``caps.bit_exact`` (float GQA + safe softmax only) and
silently fell back to whole-prompt prefill / FIFO blocking everywhere
else.  The cache-extend program replays any token window through the
*prefill* math against the populated cache, so every datapath — GQA,
MLA latent caches, int8-KV, LUT softmax — now runs all three features
for real.  This layer pins that down:

* **Chunked parity**: chunk-admitted engines token-identical to the
  whole-prompt engine on every datapath x {dense, paged}, with the
  extend program actually dispatching.
* **Prefix-skip parity**: a full-coverage hit skips the prompt-prefill
  dispatch entirely (0 new prefill dispatches) and still reproduces the
  cold stream, on MLA and int8-KV.
* **Preemption-resume parity**: an oversubscribed pool preempts and
  resumes on MLA / int8-KV with streams equal to the dense engine.
* **Program budget**: with every knob on, the jit cache holds exactly
  ``len(prefill_buckets)`` prefill + 1 decode + 1 extend programs
  (CI runs this next to the other budget tests).
* **Loud fallbacks** (satellites): engines that *cannot* honor a
  requested feature say so — ``disabled_features`` telemetry + a
  one-shot RuntimeWarning — and ``prefill_chunk`` on a non-bucketable
  (SSM / rolling-window) engine is a configuration error, not a silent
  no-op.
"""

import warnings

import jax
import numpy as np
import pytest

from repro import configs
from repro.configs.base import ServeConfig
from repro.core import precision as P
from repro.models import lm
from repro.serve import Engine

KEY = jax.random.PRNGKey(7)

KV8 = P.PrecisionPolicy(
    "kv8", (P.Rule("kv_cache", P.int8(per_channel=False)),)
)
LUT_KV8 = P.PrecisionPolicy("lut_kv8", (
    P.Rule("layers.*.attn.softmax", P.lut8()),
    P.Rule("kv_cache", P.int8(per_channel=False)),
))

# the datapaths the old gate silently excluded
DATAPATHS = [
    ("minicpm3-4b", None),      # MLA latent cache
    ("granite-8b", KV8),        # int8-KV GQA
    ("granite-8b", LUT_KV8),    # LUT softmax + int8-KV
]


def _setup(arch):
    cfg = configs.get_config(arch, reduced=True)
    return cfg, lm.init_params(cfg, KEY)


def _serve(policy, **kw):
    base = dict(max_batch=2, max_seq_len=64, decode_steps=3,
                prefill_buckets=(8, 16, 32), policy=policy)
    base.update(kw)
    return ServeConfig(**base)


def _gen(cfg, params, sc, prompts, n_new=6):
    eng = Engine(cfg, params, sc)
    handles = [eng.submit(list(p), max_new_tokens=n_new) for p in prompts]
    res = eng.generate()
    return eng, [res[h.uid].generated for h in handles]


def _prompts(cfg, lengths=(20, 11), seed=0):
    rng = np.random.default_rng(seed)
    return [list(rng.integers(1, cfg.vocab_size - 1, n)) for n in lengths]


@pytest.mark.parametrize("arch,policy", DATAPATHS,
                         ids=["mla", "int8kv", "lut_int8kv"])
@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_chunked_prefill_parity(arch, policy, layout):
    cfg, params = _setup(arch)
    prompts = _prompts(cfg)
    kw = dict(kv_layout=layout, kv_page_size=8) if layout == "paged" else {}
    _, ref = _gen(cfg, params, _serve(policy, cache_extend=False, **kw),
                  prompts)
    eng, got = _gen(cfg, params, _serve(policy, prefill_chunk=8, **kw),
                    prompts)
    assert got == ref, f"{arch}/{layout}: chunked diverged from whole-prompt"
    assert eng.scheduler.chunk_len == 8
    assert eng.telemetry["extend_dispatches"] >= 1
    assert eng.telemetry["disabled_features"] == []
    # the 20-token prompt never minted its whole-length bucket program
    assert 32 not in eng.executor._prefill_fn


@pytest.mark.parametrize("arch,policy", DATAPATHS,
                         ids=["mla", "int8kv", "lut_int8kv"])
def test_prefix_skip_full_coverage_skips_prefill(arch, policy):
    """A warm full-coverage hit must not dispatch prompt prefill at all:
    the shared pages are mapped and only the unwritten tail rides the
    extend program — with the stream identical to the cold run."""
    cfg, params = _setup(arch)
    prompt = _prompts(cfg, lengths=(16,))[0]  # exactly 2 full pages
    sc = _serve(policy, kv_layout="paged", kv_page_size=8,
                kv_prefix_cache=True)
    eng = Engine(cfg, params, sc)
    h1 = eng.submit(list(prompt), max_new_tokens=6)
    cold = eng.generate()[h1.uid].generated
    dispatches_before = eng.telemetry["prefill_dispatches"]
    h2 = eng.submit(list(prompt), max_new_tokens=6)
    warm = eng.generate()[h2.uid].generated
    assert warm == cold, f"{arch}: prefix-skip resume diverged"
    assert eng.telemetry["prefill_dispatches"] == dispatches_before, (
        f"{arch}: full-coverage hit still dispatched prompt prefill"
    )
    assert eng.telemetry["prefill_tokens_saved"] > 0
    eng.executor.cache_mgr.check_invariants()


@pytest.mark.parametrize("arch,policy", DATAPATHS,
                         ids=["mla", "int8kv", "lut_int8kv"])
def test_preemption_resume_parity(arch, policy):
    """An oversubscribed pool preempts the youngest resident; its resume
    replays the prompt with prefill math and the generated tail with
    decode math — byte-for-byte the cache the dense engine would hold."""
    cfg, params = _setup(arch)
    prompts = ([7, 8, 9], [1, 2, 3])
    kw = dict(max_seq_len=32,)
    _, dense = _gen(cfg, params, _serve(policy, **kw), prompts, n_new=20)
    eng, paged = _gen(
        cfg, params,
        _serve(policy, kv_layout="paged", kv_page_size=8, kv_pages=5,
               kv_preemption=True, **kw),
        prompts, n_new=20,
    )
    assert paged == dense, f"{arch}: preempt-resume diverged from dense"
    assert eng.telemetry["preemptions"] >= 1, f"{arch}: pool never preempted"
    assert eng.telemetry["disabled_features"] == []
    eng.executor.cache_mgr.check_invariants()


def test_float_gqa_on_tpu_resumes_with_prefill_math(monkeypatch):
    """On a TPU the decode scan is not bitwise the prefill, so a float
    GQA engine built there replays a preemption resume's prompt span with
    prefill math and only its generated span through the decode scan —
    and still matches the dense engine."""
    cfg, params = _setup("granite-8b")
    prompts = ([7, 8, 9], [1, 2, 3])
    kw = dict(max_seq_len=32)
    _, dense = _gen(cfg, params, _serve(None, **kw), prompts, n_new=20)
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        eng = Engine(cfg, params, _serve(
            None, kv_layout="paged", kv_page_size=8, kv_pages=5,
            kv_preemption=True, kv_prefix_cache=True, **kw,
        ))
    assert not eng.executor.bit_exact and eng.scheduler.extend_replay
    handles = [eng.submit(list(p), max_new_tokens=20) for p in prompts]
    res = eng.generate()
    assert [res[h.uid].generated for h in handles] == dense
    assert eng.telemetry["preemptions"] >= 1
    eng.executor.cache_mgr.check_invariants()


def test_jit_program_budget_with_extend():
    """The one new program is ONE program: with chunking, prefix sharing
    and preemption all on, on an extend datapath (MLA), the jit caches
    hold exactly len(prefill_buckets) prefill + 1 decode + 1 extend."""
    cfg, params = _setup("minicpm3-4b")
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(1, cfg.vocab_size - 1, n))
               for n in (3, 5, 9, 12, 17, 23, 30)]
    prompts += [list(prompts[0])]  # one full-coverage repeat
    sc = ServeConfig(
        max_batch=4, max_seq_len=64, decode_steps=3,
        prefill_buckets=(8, 16), prefill_chunk=8,
        kv_layout="paged", kv_page_size=8,
        kv_prefix_cache=True, kv_preemption=True,
    )
    eng, streams = _gen(cfg, params, sc, prompts, n_new=5)
    assert all(len(s) == 5 for s in streams)

    def programs(fn):
        size = getattr(fn, "_cache_size", None)
        return size() if callable(size) else 1

    buckets = eng.executor.buckets
    prefill = sum(programs(f) for f in eng.executor._prefill_fn.values())
    assert prefill <= len(buckets)
    assert programs(eng.executor._decode_fn) == 1
    assert programs(eng.executor._extend_fn) == 1
    assert prefill + programs(eng.executor._decode_fn) + programs(
        eng.executor._extend_fn
    ) <= len(buckets) + 2
    assert eng.telemetry["extend_compiles"] == 1
    assert eng.telemetry["extend_dispatches"] >= 1
    assert eng.telemetry["decode_compiles"] == 1


def test_unhonorable_features_warn_and_report():
    """With the extend program disabled, an MLA engine cannot honor
    chunking / prefill-skip / preemption: it must say so once via
    RuntimeWarning and permanently in ``disabled_features`` telemetry —
    never silently."""
    cfg, params = _setup("minicpm3-4b")
    sc = _serve(None, cache_extend=False, prefill_chunk=8,
                kv_layout="paged", kv_page_size=8,
                kv_prefix_cache=True, kv_preemption=True)
    with pytest.warns(RuntimeWarning):
        eng = Engine(cfg, params, sc)
    disabled = eng.telemetry["disabled_features"]
    joined = " ".join(disabled)
    assert "prefill_chunk" in joined
    assert "kv_preemption" in joined
    assert "prefill-skip" in joined
    # the engine still serves correctly, just without the features
    h = eng.submit(list(range(1, 20)), max_new_tokens=4)
    assert len(eng.generate()[h.uid].generated) == 4
    assert eng.scheduler.chunk_len is None
    assert eng.telemetry["extend_dispatches"] == 0


def test_fully_honored_engine_reports_nothing_disabled():
    cfg, params = _setup("granite-8b")
    sc = _serve(KV8, prefill_chunk=8, kv_layout="paged", kv_page_size=8,
                kv_prefix_cache=True, kv_preemption=True)
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        eng = Engine(cfg, params, sc)
    assert eng.telemetry["disabled_features"] == []


def test_prefill_chunk_rejected_on_non_bucketable_engines():
    """SSM / hybrid state caches admit exact-length prompts only; a
    chunk request there is a configuration error, not a silent no-op."""
    cfg = configs.get_config("mamba2-130m", reduced=True)
    params = lm.init_params(cfg, KEY)
    with pytest.raises(ValueError, match="bucketable"):
        Engine(cfg, params,
               ServeConfig(max_batch=2, max_seq_len=64, prefill_chunk=8))
