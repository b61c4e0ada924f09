"""End-to-end serving driver (the paper's kind is inference): serve a
small LM with batched requests through the continuous-batching engine,
with the paper's quantized datapath enabled.

    PYTHONPATH=src python examples/serve_lm.py --arch granite-8b --requests 12

``--stream`` consumes two interleaved ``Engine.stream`` iterators (the
rest batch behind them) and prints per-token events with
time-to-first-token — the client-facing side of the
Scheduler / Executor / Engine split.
"""

import argparse
import time

import jax
import numpy as np

from repro import configs
from repro.launch.compile_cache import enable_compile_cache
from repro.models import lm
from repro.serve import Engine
from repro.serve.cli import add_serving_args, config_from_args, place_params


def stream_demo(eng, handles):
    """Interleave the first two streams token-by-token (proving both
    make progress on shared engine pumps), then drain the rest."""
    first_ts = {}
    live = [eng.stream(h) for h in handles[:2]]
    while live:
        for it in list(live):
            ev = next(it, None)
            if ev is None:
                live.remove(it)
            else:
                first_ts.setdefault(ev.uid, ev.ts)
                print(f"  [stream] req {ev.uid} token#{ev.index} = {ev.token}"
                      f"{'  <done:' + ev.finish_reason + '>' if ev.finished else ''}")
    for h in handles[2:]:
        for ev in eng.stream(h):
            first_ts.setdefault(ev.uid, ev.ts)
    for h in handles[:3]:
        req = eng.result(h)
        if h.uid not in first_ts:  # zero-token finish (sequence cap)
            print(f"  req {h.uid}: no tokens -> {req.generated}")
            continue
        ttft_ms = (first_ts[h.uid] - req.created_at) * 1e3
        print(f"  req {h.uid}: ttft {ttft_ms:.1f} ms -> {req.generated}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--requests", type=int, default=12)
    add_serving_args(ap, max_batch=4, max_seq=128, max_new=16,
                     temperature=0.7)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = configs.get_config(args.arch, reduced=True)
    serve_cfg = config_from_args(args, cfg)
    params = place_params(lm.init_params(cfg, jax.random.PRNGKey(0)), serve_cfg)
    eng = Engine(cfg, params, serve_cfg)
    print(f"serving {cfg.name} ({lm.count_params(cfg):,} params), "
          f"max_batch={args.max_batch}, policy={eng.executor.policy.name}, "
          f"kv_layout={eng.executor.kv_layout}, "
          f"buckets={eng.executor.buckets or 'exact'}, "
          f"decode_steps={eng.serve_cfg.decode_steps}"
          + (f", prefill_chunk={args.prefill_chunk}"
             if args.prefill_chunk else ""))

    rng = np.random.default_rng(0)
    preamble = list(rng.integers(0, cfg.vocab_size, args.shared_prefix))
    handles = []
    for _ in range(args.requests):
        prompt = preamble + list(
            rng.integers(0, cfg.vocab_size, rng.integers(3, 12))
        )
        handles.append(eng.submit(prompt, max_new_tokens=args.max_new))

    t0 = time.perf_counter()
    if args.stream:
        stream_demo(eng, handles)
        results = {h.uid: eng.result(h) for h in handles}
    else:
        steps = 0
        while eng.has_work:
            stats = eng.step()
            steps += 1
            if steps % 8 == 0:
                active = sum(s.active for s in eng.executor.slots)
                print(f"  step {steps}: active={active} "
                      f"queued={len(eng.scheduler.queue)} "
                      f"prefilled={stats['prefilled']} "
                      f"decoded={stats['decoded']}")
        results = {h.uid: eng.result(h) for h in handles}
    dt = time.perf_counter() - t0

    total_tokens = sum(len(r.generated) for r in results.values())
    print(f"\ncompleted {len(results)} requests / {total_tokens} tokens "
          f"in {dt:.2f}s ({total_tokens/dt:.1f} tok/s on CPU host)")
    tel = eng.telemetry
    print(f"telemetry: queue wait mean {tel['queue_wait_s_total']/max(tel['prompts_admitted'],1)*1e3:.1f} ms | "
          f"{tel['prefill_compiles']} prefill programs, "
          f"{tel['decode_compiles']} decode program | "
          f"prefill {tel['prefill_time_s']:.2f}s / decode {tel['decode_time_s']:.2f}s")
    print(f"kv cache: layout={tel['kv_layout']} "
          f"{tel['kv_bytes'] / 2**20:.2f} MiB | "
          f"pages peak {tel['pages_in_use_peak']}/{tel['pages_capacity']} "
          f"(page_size={tel['kv_page_size']})")
    if args.speculative:
        acc = tel["draft_tokens_accepted"] / max(
            tel["draft_tokens_proposed"], 1
        )
        print(f"speculative: draft={args.draft or 'self'} "
              f"k={args.spec_tokens} | "
              f"proposed {tel['draft_tokens_proposed']} / "
              f"accepted {tel['draft_tokens_accepted']} "
              f"(rate {acc:.2f}) | "
              f"{tel['spec_dispatches']} verify dispatches")
    if args.kv_prefix_cache or args.kv_preemption:
        print(f"prefix cache: hit rate {tel['prefix_hit_rate']:.2f} | "
              f"prefill tokens saved {tel['prefill_tokens_saved']} "
              f"(+{tel['prefix_tokens_shared']} shared-storage) | "
              f"{tel['cow_copies']} CoW copies | "
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
        print("phases (ms):")
        for name, s in tel["phases"].items():
            if not isinstance(s, dict):
                continue
            print(f"  {name:>10}: p50 {s['p50_ms']:7.2f} | "
                  f"p95 {s['p95_ms']:7.2f} | p99 {s['p99_ms']:7.2f} | "
                  f"total {s['total_s']:.2f}s over {s['n']} steps")
    if not args.stream:
        for h in handles[:3]:
            r = results[h.uid]
            print(f"  req {h.uid}: prompt {r.prompt[:6]}... -> {r.generated}")


if __name__ == "__main__":
    main()
