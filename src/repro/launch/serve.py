"""Serving launcher: continuous-batching engine with the paper's
quantized datapath, fed from a simple request file or synthetic load.

    PYTHONPATH=src python -m repro.launch.serve --arch granite-8b --quantized

Without ``--full-config`` the arch runs at its reduced smoke shape.  With
it the default ``granite-8b`` is about 8.2 B parameters (16.5 GB in
bf16) and does not fit one TPU v5e's 16 GB of HBM; ``minicpm-2b`` does
(see ``chip_smoke.py`` for a pool size that fits beside it).

All engine flags come from the shared serving CLI (serve/cli.py);
``--stream`` switches from the batch ``Engine.generate`` wrapper to
per-token ``Engine.stream`` consumption and reports time-to-first-token.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import configs
from repro.launch.compile_cache import enable_compile_cache
from repro.models import lm
from repro.serve import Engine, ReplicaRouter
from repro.serve.cli import (  # noqa: F401  (resolve_policy_arg re-export)
    add_serving_args,
    config_from_args,
    resolve_policy_arg,
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--full-config", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    add_serving_args(ap, max_batch=4, max_seq=128, max_new=16,
                     temperature=0.0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = configs.get_config(args.arch, reduced=not args.full_config)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    serve_cfg = config_from_args(args, cfg)
    # replicas > 1: the same request-lifecycle API, fronted by the
    # least-loaded data-parallel router (serve/router.py)
    eng = (
        ReplicaRouter(cfg, params, serve_cfg)
        if serve_cfg.replicas > 1
        else Engine(cfg, params, serve_cfg)
    )
    rng = np.random.default_rng(0)
    preamble = list(rng.integers(0, cfg.vocab_size, args.shared_prefix))
    handles = [
        eng.submit(
            preamble
            + list(rng.integers(0, cfg.vocab_size, rng.integers(4, 16))),
            max_new_tokens=args.max_new,
        )
        for _ in range(args.requests)
    ]
    t0 = time.perf_counter()
    if args.stream:
        ttfts, toks = [], 0
        for h in handles:
            events = list(eng.stream(h))
            toks += len(events)
            if events:  # a request can legally finish with zero tokens
                # created_at, not submitted_at: preemption restamps the
                # latter for queue-wait accounting
                ttfts.append(events[0].ts - eng.request(h).created_at)
        dt = time.perf_counter() - t0
        print(f"{len(handles)} requests streamed, {toks} tokens in {dt:.2f}s "
              f"({toks/dt:.1f} tok/s) | "
              f"ttft p50 {np.percentile(ttfts, 50)*1e3:.1f} ms / "
              f"p95 {np.percentile(ttfts, 95)*1e3:.1f} ms"
              if ttfts else
              f"{len(handles)} requests streamed, {toks} tokens in {dt:.2f}s")
    else:
        results = eng.generate()
        dt = time.perf_counter() - t0
        toks = sum(len(results[h.uid].generated) for h in handles)
        print(f"{len(handles)} requests, {toks} tokens in {dt:.2f}s "
              f"({toks/dt:.1f} tok/s host throughput)")
    if isinstance(eng, ReplicaRouter):
        fleet = eng.telemetry
        print(f"router: {fleet['replicas']} replicas | "
              f"{fleet['tokens_generated']} tokens total | "
              "per-replica admitted "
              f"{[t['prompts_admitted'] for t in fleet['replica_telemetry']]}")
        eng = eng.engines[0]  # detailed prints: the first replica's view
    tel = eng.telemetry
    queue_wait_ms = (
        tel["queue_wait_s_total"] / max(tel["prompts_admitted"], 1) * 1e3
    )
    mode = "async (pipelined)" if eng.serve_cfg.async_loop else "sync"
    print(f"engine loop: {mode}"
          + (" | mesh-sharded decode" if eng.serve_cfg.shard_decode else ""))
    print(f"telemetry: policy={eng.executor.policy.name} | "
          f"queue wait mean {queue_wait_ms:.1f} ms | "
          f"{tel['prefill_compiles']} prefill programs "
          f"(buckets={eng.executor.buckets or 'exact'}"
          f"{f', chunk={args.prefill_chunk}' if args.prefill_chunk else ''}), "
          f"{tel['decode_compiles']} decode program "
          f"(decode_steps={eng.serve_cfg.decode_steps})")
    print(f"kv cache: layout={tel['kv_layout']} "
          f"{tel['kv_bytes'] / 2**20:.2f} MiB | "
          f"pages {tel['pages_in_use']}/{tel['pages_capacity']} in use "
          f"(peak {tel['pages_in_use_peak']}, "
          f"page_size={tel['kv_page_size']})")
    if args.kv_prefix_cache or args.kv_preemption:
        print(f"prefix cache: hit rate {tel['prefix_hit_rate']:.2f} "
              f"({tel['prefix_hits']}/{tel['prefix_queries']}) | "
              f"prefill tokens saved {tel['prefill_tokens_saved']} "
              f"(+{tel['prefix_tokens_shared']} shared-storage) | "
              f"{tel['pages_cached']} pages retained, "
              f"{tel['cow_copies']} CoW copies, "
              f"{tel['page_evictions']} evictions | "
              f"{tel['preemptions']} preemptions")
    if getattr(args, "kv_host_pages", 0):
        print(f"victim tier: {tel['swap_outs']} spills / "
              f"{tel['swap_ins']} swap-ins | "
              f"host pages {tel['host_pages_used']}/"
              f"{tel['host_pages_capacity']} "
              f"({tel['host_evictions']} tier evictions) | "
              f"swap time {tel['swap_latency_s']*1e3:.1f} ms")
    if args.scheduler == "edf" or args.deadline_ms is not None:
        print(f"slo: scheduler={args.scheduler} | "
              f"{tel['deadline_requests']} deadlined requests, "
              f"{tel['deadline_missed']} missed "
              f"({tel['deadline_dropped']} dropped)")
    if tel["phases"]:
        print("phases (ms): " + " | ".join(
            f"{name} p50 {s['p50_ms']:.2f} / p95 {s['p95_ms']:.2f}"
            for name, s in tel["phases"].items() if isinstance(s, dict)
        ))
        if "overlap_efficiency" in tel["phases"]:
            ph = tel["phases"]
            print(f"overlap: device hidden {ph['device_overlap_s']:.3f}s | "
                  f"host bubble {ph['host_bubble_s']:.3f}s | "
                  f"efficiency {ph['overlap_efficiency']:.3f}")


if __name__ == "__main__":
    main()
