"""Declarative benchmark matrix: configs x policies x kv-layouts x
ablations, with per-cell result caching and a regression gate.

Every serving feature so far (paged KV, prefix cache, chunked prefill,
the cache-extending prefill program) shipped with its own one-off
benchmark invocation; nothing measured the *cross product*, so a change
that helped one cell could quietly tax another.  This runner makes the
grid explicit:

* A **cell** is ``config/policy/layout/ablation``.  Ablations switch
  one feature off against the full-featured engine:

  - ``none``       — everything on (prefix cache + preemption, chunked
    prefill, cache-extend) as the layout allows
  - ``no-prefix``  — prefix-cache page sharing off
  - ``no-paging``  — dense slabs instead of the block-table pool
    (which also forecloses sharing/preemption)
  - ``no-chunk``   — chunked prefill off (long prompts admit whole)
  - ``no-extend``  — cache-extending prefill program off (the old
    bit-exact-gated fallback)

* **Per-cell caching**: results land in ``benchmarks/.matrix_cache/``
  keyed by a content hash of the ``.py`` files under ``src/`` and
  ``benchmarks/`` + cell, so a cell is reused only by the exact code that
  measured it, in a git checkout or not (``--no-cache`` forces fresh
  measurements).

* ``--record`` appends a timestamped entry (git rev + UTC date + args +
  every cell row) to the ``BENCH_matrix.json`` trajectory — append-only,
  same schema discipline as ``BENCH_serving.json``.

* ``--check`` compares fresh measurements against the *latest* recorded
  entry and exits nonzero when any shared cell's ``us_per_token``
  regressed by more than ``--tolerance`` (default 0.2 = 20%).  CI runs
  the 2-cell ``--preset smoke`` with a generous tolerance — a tripwire
  for order-of-magnitude regressions, not a microbenchmark gate.

CSV rows: ``matrix,<cell>,<us_per_token>,<derived>``.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import time
from pathlib import Path

import numpy as np

from repro import configs
from repro.configs.base import ServeConfig
from repro.models import lm

_HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(_HERE)
CACHE_DIR = os.path.join(_HERE, ".matrix_cache")
DEFAULT_TRAJECTORY = os.path.join(ROOT, "BENCH_matrix.json")


# --------------------------------------------------------------- cells --
@dataclasses.dataclass(frozen=True)
class Cell:
    """One matrix point.  ``policy`` None means the float datapath."""

    config: str = "physics_scale"
    policy: str | None = None
    layout: str = "paged"
    ablation: str = "none"

    @property
    def key(self) -> str:
        return (
            f"{self.config}/{self.policy or 'float'}/"
            f"{self.layout}/{self.ablation}"
        )


ABLATIONS = ("none", "no-prefix", "no-paging", "no-chunk", "no-extend")

#: named cell sets.  smoke = the 2-cell CI tripwire; default = the
#: physics-scale grid both datapaths x both layouts plus every ablation
#: on the quantized paged engine (the cells the recent PRs changed).
PRESETS: dict[str, tuple[Cell, ...]] = {
    "smoke": (
        Cell("physics_scale", None, "paged", "none"),
        Cell("physics_scale", "int8_serve", "paged", "no-extend"),
    ),
    "default": (
        Cell("physics_scale", None, "dense", "none"),
        Cell("physics_scale", None, "paged", "none"),
        Cell("physics_scale", "int8_serve", "dense", "none"),
        Cell("physics_scale", "int8_serve", "paged", "none"),
        Cell("physics_scale", "int8_serve", "paged", "no-prefix"),
        Cell("physics_scale", "int8_serve", "paged", "no-paging"),
        Cell("physics_scale", "int8_serve", "paged", "no-chunk"),
        Cell("physics_scale", "int8_serve", "paged", "no-extend"),
        Cell("minicpm_2b", None, "paged", "none"),
        Cell("minicpm_2b", "int8_serve", "paged", "none"),
    ),
}


def _model_cfg(name: str):
    if name == "physics_scale":
        from benchmarks.serving_throughput import physics_scale_lm

        return physics_scale_lm()
    return configs.get_config(name.replace("_", "-"), reduced=True)


def _serve_cfg(cell: Cell, policy: str | None) -> ServeConfig:
    """Resolve a cell to engine knobs: the full feature set, minus the
    one thing its ablation switches off (layout permitting)."""
    if cell.ablation not in ABLATIONS:
        raise ValueError(
            f"unknown ablation {cell.ablation!r}; expected one of {ABLATIONS}"
        )
    layout = "dense" if cell.ablation == "no-paging" else cell.layout
    paged = layout == "paged"
    sharing = paged and cell.ablation != "no-prefix"
    return ServeConfig(
        max_batch=2,
        max_seq_len=64,
        prefill_buckets=(8, 16, 32),
        decode_steps=4,
        policy=policy,
        kv_layout=layout,
        kv_page_size=16,
        kv_prefix_cache=sharing,
        kv_preemption=sharing,
        prefill_chunk=None if cell.ablation == "no-chunk" else 8,
        cache_extend=cell.ablation != "no-extend",
    )


# ------------------------------------------------------------- measure --
def measure_cell(cell: Cell, n_requests: int = 8, max_new: int = 16,
                 seed: int = 0) -> dict:
    """Run one cell: warmup wave (compiles the program set), then a
    measured prefix-heavy wave — every feature under ablation has work
    to do (a shared preamble exercises the prefix cache, a long prompt
    exercises chunking).  Returns the cell's result record."""
    from repro.serve import Engine

    cfg = _model_cfg(cell.config)
    policy = cfg.serve_policy if cell.policy == "auto" else cell.policy
    eng = Engine(
        cfg, params_for(cell.config), _serve_cfg(cell, policy), seed=seed
    )

    preamble = list(
        np.random.default_rng(seed + 7).integers(0, cfg.vocab_size, 16)
    )

    def wave(wave_seed):
        rng = np.random.default_rng(wave_seed)
        for k in range(n_requests):
            # one long prompt per wave so chunked prefill runs
            n = 40 if k == 0 else int(rng.integers(3, 14))
            payload = list(rng.integers(0, cfg.vocab_size, n))
            eng.submit(preamble + payload, max_new_tokens=max_new)
        t0 = time.perf_counter()
        eng.generate()
        return time.perf_counter() - t0

    wave(seed)
    tokens_before = eng.telemetry["tokens_generated"]
    wall_s = wave(seed + 1)
    tel = eng.telemetry
    toks = tel["tokens_generated"] - tokens_before
    return {
        "cell": cell.key,
        "us_per_token": round(wall_s / max(toks, 1) * 1e6, 1),
        "tok_s": round(toks / max(wall_s, 1e-9), 1),
        "prefill_compiles": tel["prefill_compiles"],
        "decode_compiles": tel["decode_compiles"],
        "extend_dispatches": tel.get("extend_dispatches", 0),
        "prefill_tokens_saved": tel.get("prefill_tokens_saved", 0),
        "kv_layout": tel["kv_layout"],
    }


_PARAMS_CACHE: dict[str, object] = {}


def params_for(config: str):
    """Init params once per model config per process (cells share them)."""
    if config not in _PARAMS_CACHE:
        import jax

        _PARAMS_CACHE[config] = lm.init_params(
            _model_cfg(config), jax.random.PRNGKey(0)
        )
    return _PARAMS_CACHE[config]


# --------------------------------------------------------------- cache --
def _git_rev() -> str:
    from benchmarks.serving_throughput import _git_rev as rev

    return rev()


def code_hash(root: str = ROOT) -> str:
    """Content hash of every ``.py`` file under ``src/`` and
    ``benchmarks/`` of the checkout at ``root`` (paths and bytes)."""
    h = hashlib.sha256()
    base = Path(root)
    for sub in ("src", "benchmarks"):
        for path in sorted((base / sub).rglob("*.py")):
            h.update(str(path.relative_to(base)).encode() + b"\0")
            h.update(path.read_bytes())
    return h.hexdigest()[:16]


def _cache_path(key: str, cell: Cell) -> str:
    return os.path.join(CACHE_DIR, key, cell.key.replace("/", "__") + ".json")


def run_cells(cells: tuple[Cell, ...], *, use_cache: bool = True,
              verbose: bool = False) -> list[dict]:
    """Measure every cell, reading/writing the per-code-hash disk cache.
    A cached cell is a measurement taken by this exact code — safe to
    reuse; any edit to a source file moves the hash and invalidates it."""
    key = code_hash()
    results = []
    for cell in cells:
        path = _cache_path(key, cell)
        if use_cache and os.path.exists(path):
            with open(path) as f:
                rec = json.load(f)
            rec["cached"] = True
        else:
            if verbose:
                print(f"# measuring {cell.key} ...")
            rec = measure_cell(cell)
            rec["cached"] = False
            if use_cache:
                os.makedirs(os.path.dirname(path), exist_ok=True)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=2)
        results.append(rec)
    return results


# ---------------------------------------------------------- trajectory --
def load_trajectory(path: str) -> list[dict]:
    if not os.path.exists(path):
        return []
    with open(path) as f:
        doc = json.load(f)
    return [doc] if isinstance(doc, dict) else list(doc)


def record(path: str, preset: str, results: list[dict]) -> dict:
    """Append one timestamped matrix run to the trajectory at ``path``."""
    import datetime

    entry = {
        "bench": "matrix",
        "date": datetime.datetime.now(datetime.timezone.utc).isoformat(
            timespec="seconds"
        ),
        "git_rev": _git_rev(),
        "args": {"preset": preset},
        "cells": [
            {k: v for k, v in rec.items() if k != "cached"}
            for rec in results
        ],
    }
    history = load_trajectory(path)
    history.append(entry)
    with open(path, "w") as f:
        json.dump(history, f, indent=2)
        f.write("\n")
    return entry


def check(results: list[dict], baseline_entry: dict,
          tolerance: float = 0.2) -> list[str]:
    """Compare fresh cell results against a recorded entry; return one
    failure line per shared cell whose us_per_token regressed by more
    than ``tolerance`` (0.2 = 20% slower than baseline fails).  Cells
    missing on either side are skipped — the gate only judges what both
    runs measured."""
    base = {rec["cell"]: rec for rec in baseline_entry.get("cells", [])}
    failures = []
    for rec in results:
        ref = base.get(rec["cell"])
        if ref is None:
            continue
        limit = ref["us_per_token"] * (1.0 + tolerance)
        if rec["us_per_token"] > limit:
            failures.append(
                f"{rec['cell']}: {rec['us_per_token']:.1f} us/tok vs "
                f"baseline {ref['us_per_token']:.1f} "
                f"(limit {limit:.1f}, tolerance {tolerance:.0%})"
            )
    return failures


# ----------------------------------------------------------------- cli --
def _rows(results: list[dict]) -> list[str]:
    rows = ["bench,cell,us_per_token,derived"]
    for rec in results:
        derived = ";".join(
            f"{k}={v}" for k, v in rec.items()
            if k not in ("cell", "us_per_token")
        )
        rows.append(f"matrix,{rec['cell']},{rec['us_per_token']},{derived}")
    return rows


def run(preset: str = "smoke") -> list[str]:
    """benchmarks/run.py entry point: the smoke cells, uncached."""
    return _rows(run_cells(PRESETS[preset], use_cache=False))


def main():
    import argparse

    ap = argparse.ArgumentParser(
        description="serving benchmark matrix (configs x policies x "
                    "layouts x ablations) with caching + regression gate"
    )
    ap.add_argument("--preset", default="default", choices=sorted(PRESETS),
                    help="which cell set to run (smoke = 2-cell CI "
                         "tripwire, default = the full ablation grid)")
    ap.add_argument("--no-cache", action="store_true",
                    help="ignore the per-code-hash cell cache and re-measure")
    ap.add_argument("--record", nargs="?", const=DEFAULT_TRAJECTORY,
                    default=None, metavar="PATH",
                    help="append this run to the trajectory JSON "
                         f"(default {DEFAULT_TRAJECTORY})")
    ap.add_argument("--check", nargs="?", const=DEFAULT_TRAJECTORY,
                    default=None, metavar="PATH",
                    help="compare against the latest entry in the "
                         "trajectory JSON; exit 1 on regression")
    ap.add_argument("--tolerance", type=float, default=0.2,
                    help="allowed us_per_token regression for --check "
                         "(0.2 = 20%%; CI smoke uses a generous value — "
                         "shared-runner noise is not a regression)")
    args = ap.parse_args()

    t0 = time.time()
    results = run_cells(
        PRESETS[args.preset], use_cache=not args.no_cache, verbose=True
    )
    for row in _rows(results):
        print(row)
    if args.record:
        entry = record(args.record, args.preset, results)
        print(f"# appended run {entry['git_rev']}@{entry['date']} to "
              f"{args.record} ({len(load_trajectory(args.record))} entries)")
    if args.check:
        history = load_trajectory(args.check)
        if not history:
            raise SystemExit(f"--check: no baseline at {args.check}")
        failures = check(results, history[-1], tolerance=args.tolerance)
        if failures:
            print(f"# REGRESSION vs {history[-1].get('git_rev')}"
                  f"@{history[-1].get('date')}:")
            for line in failures:
                print(f"#   {line}")
            raise SystemExit(1)
        print(f"# check OK vs {history[-1].get('git_rev')}"
              f"@{history[-1].get('date')} "
              f"(tolerance {args.tolerance:.0%})")
    print(f"# matrix done in {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
