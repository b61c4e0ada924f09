"""Benchmark orchestrator — one benchmark per paper table/figure.

    latency_tables      <-> paper Tables II-IV   (latency vs reuse factor)
    auc_vs_bits         <-> paper Figs. 9-11     (fidelity vs fractional bits)
    resources           <-> paper Figs. 12-14    (resources vs reuse factor)
    kernel_micro        <-> per-kernel validation
    roofline_table      <-> EXPERIMENTS.md §Roofline (from the dry-run cache)
    serving_throughput  <-> engine v2 tokens/s (batch x bucket x decode_steps)
    matrix              <-> configs x policies x layouts x ablations grid

Prints ``name,us_per_call,derived`` style CSV blocks per benchmark.
"""

from __future__ import annotations

import sys
import time


def main() -> None:
    from benchmarks import (
        auc_vs_bits,
        kernel_micro,
        latency_tables,
        matrix,
        resources,
        roofline_table,
        serving_throughput,
    )
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    benches = [
        ("latency_tables", latency_tables.run),
        ("resources", resources.run),
        ("kernel_micro", kernel_micro.run),
        ("auc_vs_bits", auc_vs_bits.run),
        ("roofline_table", roofline_table.run),
        ("serving_throughput", serving_throughput.run),
        ("matrix", matrix.run),
    ]
    only = sys.argv[1] if len(sys.argv) > 1 else None
    failures = 0
    for name, fn in benches:
        if only and name != only:
            continue
        t0 = time.time()
        print(f"===== {name} =====")
        try:
            for row in fn():
                print(row)
            print(f"# {name}: OK in {time.time()-t0:.1f}s")
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"# {name}: FAILED {type(e).__name__}: {e}")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
