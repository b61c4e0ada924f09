#!/usr/bin/env python3
"""Serve minicpm-2b at its published widths on a TPU and check the answers.

    python chip_smoke.py                # phases A-D on one chip
    python chip_smoke.py --four-chips   # mesh-sharded decode on four chips

The model is minicpm-2b at full width and full depth (40 layers, d_model
2304, 36 heads, d_ff 5760, vocab 122753, bf16 weights) with random
weights drawn from ``--seed``.  Every phase goes through the serving
``Engine`` a user would call, and checks the engine's own invariants:

* A — a dense float engine serves 16 requests (prompts of 32-400 tokens,
  half of them behind one shared preamble, 32 new tokens each) through
  ``Engine.generate``, then three of them again through ``Engine.stream``;
  the streamed tokens equal the batch ones.  Half the requests are
  greedy, half sample at temperature 1 with their own seed: random
  weights make minicpm-2b's greedy output echo its last input token, so
  only the seeded streams depend on the whole logit vector, and with it
  on what the KV cache holds.
* B — the same requests on the paged layout with the prefix cache,
  preemption and the pipelined loop on; the prefix cache must hit,
  preemption must fire, and every token equals Phase A's (a seeded
  stream is keyed by (seed, position), so no rescheduling may move it).
* C — the ``int8_serve`` precision policy (int8 weights and KV, LUT
  softmax) serves the requests to completion with finite logits.
* D — the Pallas attention kernel, compiled (not interpreted), in ``safe``
  mode under the float policy and in ``lut`` mode under ``int8_serve``.
  Each is compared with its XLA twin on the same weights: the first
  generated tokens agree, and prefill logits agree within
  ``LOGIT_RTOL`` of the twin's largest logit.  The twin's distance from
  itself at ``highest`` matmul precision is printed beside it, as the
  noise floor of bf16 computation.

``--four-chips`` runs only the mesh path: an engine with
``shard_decode=True``, its parameters committed to the four chips, which
it splits tensor parallel over (a (1, 4) mesh), against a one-chip engine
in the same process; the KV pool and the parameters must span all four
chips, the greedy tokens must be equal, and prefill logits agree within
``LOGIT_RTOL``.

The script refuses to run on anything but a TPU.  Every check raises, so a
failing run exits non-zero; only a passing run prints, as its last line of
standard output, ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

ARCH = "minicpm-2b"
#: one v5e chip (16 GB HBM) holds 5.4 GB of bf16 weights plus the f32 KV
#: pool; a dense decode program keeps about three copies of the pool, and
#: head_dim 64 pads to 128 lanes, so 4 x 512 tokens is what fits
#: (8 x 512 overflows HBM by 4.6 GB at compile time)
BASE = dict(max_batch=4, max_seq_len=512, prefill_buckets=(128, 512),
            temperature=0.0)
N_REQUESTS = 16
NEW_TOKENS = 32
PROMPT_LEN = (32, 400)
PREAMBLE = 96  # six 16-token pages shared by half the requests
#: pages of the Phase B pool: fewer than four long requests need, so
#: admission has to preempt
KV_PAGES = 48
D_REQUESTS = 4
D_PROMPT_LEN = (40, 120)  # the 128 bucket only
D_NEW_TOKENS = 8
PALLAS = {"use_pallas": True}  # the compiled Mosaic kernel
#: Pallas vs XLA prefill logits: |diff| <= LOGIT_RTOL * max |XLA logits|.
#: The kernel accumulates softmax blockwise in f32 where XLA reduces whole
#: rows, and 40 layers write a bf16 residual stream (eps 2^-8), so each
#: layer may round differently; a few bf16 ulps of the logit scale
LOGIT_RTOL = 0.02


def make_requests(rng, vocab, n, lengths, preamble=0, sampled=False):
    """``n`` seeded ``(prompt, SamplingParams)`` pairs with prompt lengths
    drawn from ``lengths``.  Requests 0, 1 of every four start with a
    shared ``preamble``-token prefix; with ``sampled`` every odd request
    samples at temperature 1 with its own seed, the rest are greedy, so
    each mix of the two occurs."""
    from repro.serve import SamplingParams

    shared = rng.integers(0, vocab, preamble).tolist()
    requests = []
    for i in range(n):
        length = int(rng.integers(lengths[0], lengths[1] + 1))
        head = shared if i % 4 < 2 and length > preamble else []
        prompt = head + rng.integers(0, vocab, length - len(head)).tolist()
        seeded = sampled and i % 2 == 1
        requests.append((prompt, SamplingParams(
            max_new_tokens=NEW_TOKENS, temperature=1.0 if seeded else 0.0,
            seed=i if seeded else None,
        )))
    return requests


def serve(eng, requests):
    """Submit ``(prompt, SamplingParams)`` requests and run the engine to
    completion; returns each request's generated tokens in order."""
    handles = [eng.submit(p, sp) for p, sp in requests]
    t0 = time.perf_counter()
    done = eng.generate()
    wall = time.perf_counter() - t0
    tokens = [list(done[h.uid].generated) for h in handles]
    print(f"  served {len(requests)} requests, {sum(map(len, tokens))} "
          f"tokens in {wall:.2f} s wall (compilation included)")
    if any(len(t) != sp.max_new_tokens for t, (_, sp) in zip(tokens, requests)):
        raise AssertionError("a request finished short of its token budget")
    return tokens


def compile_counts(eng) -> dict:
    """Programs each jitted engine function has compiled."""
    ex = eng.executor
    counts = {
        "prefill": sum(fn._cache_size() for fn in ex._prefill_fn.values()),
        "decode": ex._decode_fn._cache_size(),
        "extend": ex._extend_fn._cache_size() if ex._extend_fn else 0,
    }
    budget = len(ex.buckets) + 2
    if sum(counts.values()) > budget:
        raise AssertionError(f"{counts} exceeds the jit budget {budget}")
    return counts


def report(name, eng, tokens):
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    print(f"  compiled programs: {compile_counts(eng)}")
    print(f"  device 0 peak_bytes_in_use {stats.get('peak_bytes_in_use')} "
          f"bytes_in_use {stats.get('bytes_in_use')}")
    for i, toks in enumerate(tokens[:4]):
        print(f"  req {i}: {toks[:8]}...")
    print(f"phase {name}: ok", flush=True)


def prefill_logits(ex, prompts, kernel, precision=None):
    """Last-position logits of ``prompts`` through the model's prefill
    forward on the executor's weights and precision plan, with the
    attention datapath chosen by ``kernel`` and the default matmul
    ``precision``.  Returns ``(logits, hlo)``, the lowered program's text
    for kernel checks."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import lm
    from repro.serve import kv_cache

    bucket = min(b for b in ex.buckets if b >= max(map(len, prompts)))
    toks = np.zeros((len(prompts), bucket), np.int32)
    for row, p in enumerate(prompts):
        toks[row, :len(p)] = p
    last = jnp.asarray([len(p) - 1 for p in prompts], jnp.int32)

    def fn(params, toks, last):
        caches = kv_cache.init_caches(
            ex.cfg, toks.shape[0], toks.shape[1], dtype=jnp.float32,
            quantized=ex.quant_cache,
        )
        logits, _, _ = lm.forward(params, ex.cfg, {"tokens": toks},
                                  mode="prefill", caches=caches, kernel=kernel)
        last_logits = jnp.take_along_axis(logits, last[:, None, None], axis=1)
        return last_logits[:, 0, :ex.cfg.vocab_size]  # drop the vocab padding

    jitted = jax.jit(fn)
    args = (ex.params, jnp.asarray(toks), last)
    with jax.default_matmul_precision(precision):
        hlo = jitted.lower(*args).as_text()
        return np.asarray(jitted(*args), np.float32), hlo


def phase_a(eng, requests):
    """Dense float engine: batch and streamed tokens."""
    tokens = serve(eng, requests)
    handles = [eng.submit(p, sp) for p, sp in requests[:3]]
    for i, h in enumerate(handles):
        t0 = eng.request(h).created_at
        events = list(eng.stream(h))
        streamed = [ev.token for ev in events]
        print(f"  stream req {i}: ttft {(events[0].ts - t0) * 1e3:.1f} ms, "
              f"{len(events)} events")
        if streamed != tokens[i]:
            raise AssertionError(f"stream req {i} differs from generate")
    report("A", eng, tokens)
    return tokens


def phase_b(eng, requests, reference):
    """Paged + prefix cache + preemption + async loop: the same tokens."""
    tokens = serve(eng, requests)
    tel = eng.telemetry
    print(f"  prefix hits {tel['prefix_hits']}/{tel['prefix_queries']}, "
          f"prefill tokens saved {tel['prefill_tokens_saved']}, "
          f"preemptions {tel['preemptions']}, "
          f"pages peak {tel['pages_in_use_peak']}/{tel['pages_capacity']}")
    for i, (got, want) in enumerate(zip(tokens, reference)):
        if got != want:
            raise AssertionError(f"req {i}: paged {got} != dense {want}")
    if tel["prefix_hits"] == 0 or tel["preemptions"] == 0:
        raise AssertionError("the prefix cache or preemption never fired")
    report("B", eng, tokens)


def phase_c(eng, requests):
    """int8_serve policy: completes, logits finite."""
    import numpy as np

    tokens = serve(eng, requests)
    prompts = [p for p, _ in requests[:D_REQUESTS]]
    logits, _ = prefill_logits(eng.executor, prompts, eng.executor.kernel)
    if not np.isfinite(logits).all():
        raise AssertionError("int8_serve prefill logits are not finite")
    print(f"  policy {eng.executor.policy.name}: softmax "
          f"{eng.executor.kernel.get('softmax_mode')}, int8 KV "
          f"{eng.executor.quant_cache}")
    report("C", eng, tokens)


def phase_d(eng, requests):
    """The engine runs the Pallas attention kernel, compiled; compare its
    prefill logits and first tokens with the XLA path on the same weights."""
    import numpy as np

    ex = eng.executor
    mode = ex.kernel.get("softmax_mode", "safe")
    tokens = serve(eng, requests)
    prompts = [p for p, _ in requests]
    pallas, hlo = prefill_logits(ex, prompts, ex.kernel)
    if "tpu_custom_call" not in hlo:
        raise AssertionError("the Pallas kernel did not lower to a TPU kernel")
    twin = {**ex.kernel, "use_pallas": False}
    xla, _ = prefill_logits(ex, prompts, twin)
    highest, _ = prefill_logits(ex, prompts, twin, precision="highest")
    for i in range(len(prompts)):
        scale = float(np.max(np.abs(xla[i])))
        diff = float(np.max(np.abs(pallas[i] - xla[i])))
        floor = float(np.max(np.abs(highest[i] - xla[i])))
        top2 = np.sort(xla[i])[-2:]
        print(f"  req {i} ({mode}): max|pallas-xla| {diff:.4g}, "
              f"max|xla@highest-xla| {floor:.4g} (max|xla| {scale:.4g}, "
              f"top-2 margin {top2[1] - top2[0]:.4g})")
        if diff > LOGIT_RTOL * scale:
            raise AssertionError(f"req {i}: logits differ by {diff:.4g}")
        first = int(np.argmax(xla[i]))
        if int(np.argmax(pallas[i])) != first or tokens[i][0] != first:
            raise AssertionError(
                f"req {i}: first token pallas {int(np.argmax(pallas[i]))} / "
                f"engine {tokens[i][0]} vs xla {first}"
            )
    report(f"D ({mode})", eng, tokens)


def init_params(cfg, seed):
    import jax

    from repro.models import lm

    t0 = time.perf_counter()
    # one program for every leaf: eagerly, each leaf's draw is its own
    # dispatch and compile
    params = jax.block_until_ready(
        jax.jit(lm.init_params, static_argnums=0)(cfg, jax.random.PRNGKey(seed))
    )
    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{n / 1e9:.2f} B parameters ({cfg.dtype}), "
          f"initialised in {time.perf_counter() - t0:.1f} s")
    return params


def one_chip(cfg, seed):
    """Phases A-D.  Engines are built one at a time and dropped before the
    next (``gc.collect`` breaks the cycle between an executor and its
    jitted bound methods, which would keep its device buffers alive)."""
    import numpy as np

    from repro.configs.base import ServeConfig
    from repro.serve import Engine

    rng = np.random.default_rng(seed)
    requests = make_requests(rng, cfg.vocab_size, N_REQUESTS, PROMPT_LEN,
                             PREAMBLE, sampled=True)
    greedy = [(p, sp) for p, sp in requests if sp.seed is None]
    d_requests = [
        (p, dataclasses.replace(sp, max_new_tokens=D_NEW_TOKENS))
        for p, sp in make_requests(rng, cfg.vocab_size, D_REQUESTS,
                                   D_PROMPT_LEN)
    ]
    print(f"prompt lengths: {[len(p) for p, _ in requests]}")
    params = init_params(cfg, seed)

    print("phase A: dense float engine")
    eng = Engine(cfg, params, ServeConfig(**BASE))
    reference = phase_a(eng, requests)
    del eng
    gc.collect()

    print("phase B: paged + prefix cache + preemption + async loop")
    eng = Engine(cfg, params, ServeConfig(
        **BASE, kv_layout="paged", kv_pages=KV_PAGES, kv_prefix_cache=True,
        kv_preemption=True, async_loop=True,
    ))
    phase_b(eng, requests, reference)
    del eng
    gc.collect()

    print("phase D: Pallas attention, float policy")
    eng = Engine(cfg, params, ServeConfig(**BASE), kernel=PALLAS)
    phase_d(eng, d_requests)
    del eng
    gc.collect()

    print("phase C: int8_serve policy")
    int8 = ServeConfig(**BASE, policy="int8_serve")
    eng = Engine(cfg, params, int8)
    # the engine holds its own int8-plan weights: dropping the float copy
    # leaves room for a second int8 engine on the same chip
    del params
    gc.collect()
    phase_c(eng, greedy)
    params = eng.executor.params
    del eng
    gc.collect()

    print("phase D: Pallas attention, int8_serve policy")
    eng = Engine(cfg, params, int8, kernel=PALLAS)
    del params
    phase_d(eng, d_requests)
    del eng
    gc.collect()


def four_chips(cfg, seed):
    """Mesh-sharded decode over four chips against one chip."""
    import jax
    import numpy as np

    from repro.configs.base import ServeConfig
    from repro.serve import Engine
    from repro.serve.cli import place_params

    rng = np.random.default_rng(seed)
    requests = make_requests(rng, cfg.vocab_size, N_REQUESTS // 2,
                             PROMPT_LEN, PREAMBLE)
    prompts = [p for p, _ in requests[:D_REQUESTS]]
    params = init_params(cfg, seed)
    print("one chip:")
    eng = Engine(cfg, params, ServeConfig(**BASE))
    reference = serve(eng, requests)
    one_logits, _ = prefill_logits(eng.executor, prompts, eng.executor.kernel)
    del eng
    gc.collect()

    sharded = ServeConfig(**BASE, shard_decode=True)
    params = place_params(params, sharded)
    eng = Engine(cfg, params, sharded)
    del params
    ex = eng.executor
    print(f"four chips: mesh {dict(ex.mesh.shape)}")
    devices = set(jax.devices())
    for name, tree in (("params", ex.params), ("kv pool", ex.caches)):
        leaves = jax.tree.leaves(tree)
        spans = {len(x.sharding.device_set) for x in leaves}
        split = sum(not x.sharding.is_fully_replicated for x in leaves)
        print(f"  {name}: {len(leaves)} arrays on {spans} devices, "
              f"{split} split across them")
        if any(x.sharding.device_set != devices for x in leaves):
            raise AssertionError(f"some {name} arrays miss a chip")
        if split == 0:
            raise AssertionError(f"every {name} array is replicated")
    for key in ("k", "v"):
        pool = ex.caches["layers"][key]
        shards = {str(s.device): s.data.shape for s in pool.addressable_shards}
        print(f"  kv pool {key} {pool.shape}: {shards}")
        if pool.sharding.is_fully_replicated:
            raise AssertionError(f"kv pool {key} is replicated, not sharded")
    tokens = serve(eng, requests)
    for i, (got, want) in enumerate(zip(tokens, reference)):
        if got != want:
            raise AssertionError(f"req {i}: mesh {got} != one chip {want}")
    logits, _ = prefill_logits(ex, prompts, ex.kernel)
    for i in range(len(prompts)):
        scale = float(np.max(np.abs(one_logits[i])))
        diff = float(np.max(np.abs(logits[i] - one_logits[i])))
        print(f"  req {i}: max|mesh-one chip| prefill logits {diff:.4g} "
              f"(max {scale:.4g})")
        if diff > LOGIT_RTOL * scale:
            raise AssertionError(f"req {i}: logits differ by {diff:.4g}")
    report("four-chip", eng, tokens)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh-sharded decode phase (4 chips)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, found {len(devices)}",
              file=sys.stderr)
        return 2

    from repro import configs
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    print(f"devices: {len(devices)} x {dev.device_kind}")
    cfg = configs.get_config(ARCH)
    (four_chips if args.four_chips else one_chip)(cfg, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
