"""Runs one cell of ``BENCHMARK.json``: finds its configuration, the
configuration's architecture (``bench/arch/<name>.py``), traffic mix and
metrics by name, serves the traffic through ``Engine`` on the cell's chips,
and checks what was served against the plain reference.

A run, in order:

1. set-up: weights from the seed in one jitted call, split over the cell's
   chips where it spans several (``bench/weights.py``), the engine, the
   mix's warm-up requests (every program the mix uses, compiled or loaded
   from the persistent cache), then a lead-in of the same traffic that
   fills the batch and the prefix cache;
2. the window: ``--seconds`` of the same traffic, timed; with ``--trace 1``
   the same traffic goes on after it while the profiler records
   ``TRACE_S`` seconds, so the window's host-clock numbers are read
   untraced in every run;
3. the drain: requests due in the window finish (at most ``drain_s``);
4. the check: a seeded sample of the finished requests, the longest among
   them, is run through the float32 reference with the weights drawn
   again, after the engine is freed, one layer at a time whole on each of
   the cell's chips.

Metrics are readers in ``bench/metrics/<name>.py``: ``read(rec)`` returns a
number, or None where the run gave it nothing to read.
"""

from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import math
import shutil
import sys
import tempfile
from pathlib import Path
from types import ModuleType, SimpleNamespace

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
#: seconds the profiler records in a traced run, after the window
TRACE_S = 5.0
#: at most this long after the window, the traced stretch starts even if a
#: request due in the window still waits for its first token
TRACE_WAIT_S = 10.0
#: the correctness sample: at least this many served tokens, at most this
#: many requests
CHECK_TOKENS = 1024
CHECK_REQUESTS = 48
#: engine counters read at the edges of the window and of the trace
COUNTERS = ("prefill_tokens_saved", "prompts_admitted", "prefix_hits",
            "prefix_queries", "preemptions", "prefill_compiles",
            "decode_compiles", "extend_compiles")


class Spec:
    """``BENCHMARK.json`` and the files it names."""

    def __init__(self, root: Path = ROOT, data: Path | None = None):
        self.root = root
        #: where the traffic mixes are (``<data>/traffic/<name>.json``),
        #: and architectures beside the benchmark's own (``<data>/arch``)
        self.data_dir = data or root / "bench"
        self.traffic_dir = self.data_dir / "traffic"
        self.data = json.loads((root / "BENCHMARK.json").read_text())

    def cell(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def metrics(self, cell: str, traced: bool) -> list[dict]:
        """The cell's end-to-end metrics, or with ``traced`` its per-layer
        ones: those that list the cell, or list no cells and move an
        end-to-end metric the cell reports."""
        e2e = [m for m in self.data["end_to_end"] if cell in m.get("workloads", [cell])]
        if not traced:
            return e2e
        names = {m["name"] for m in e2e}
        return [m for m in self.data["per_layer"]
                if cell in m.get("workloads", [cell] if m["moves"] in names else [])]

    def reader(self, name: str):
        return _load(ROOT / "bench" / "metrics" / f"{name}.py", f"bench_metric_{name}").read

    def arch(self, name: str) -> ModuleType:
        return arch(name, self.data_dir)


def _load(path: Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


#: architectures that a spec's data directory brings, loaded once each
_ARCHS: dict[Path, ModuleType] = {}


def arch(name: str, data: Path | None = None) -> ModuleType:
    """The architecture module ``bench/arch/<name>.py``, or one that a
    spec's data directory brings (``<data>/arch/<name>.py``)."""
    extra = data / "arch" / f"{name}.py" if data else None
    if extra is not None and extra.is_file():
        if extra not in _ARCHS:
            _ARCHS[extra] = _load(extra, f"bench_arch_{len(_ARCHS)}_{name}")
        return _ARCHS[extra]
    if (ROOT / "bench" / "arch" / f"{name}.py").is_file():
        return importlib.import_module(f"bench.arch.{name}")
    raise KeyError(f"no architecture {name!r}: there is no bench/arch/{name}.py")


def peaks(kind: str) -> dict:
    table = json.loads((ROOT / "bench" / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def model_config(cfg: dict):
    """The engine's ModelConfig for a configuration file, by its
    architecture."""
    return arch(cfg["architecture"]).model_config(cfg)


def serve_config(cfg: dict, **overrides):
    from repro.configs.base import ServeConfig

    eng = dict(cfg["engine"], **overrides)
    if eng.get("prefill_buckets") is not None:
        eng["prefill_buckets"] = tuple(eng["prefill_buckets"])
    return ServeConfig(**eng)


def counters(eng) -> dict:
    tel = eng.telemetry
    return {k: tel.get(k, 0) for k in COUNTERS}


class _Compiles:
    """Counts programs compiled or loaded from the persistent cache, from
    JAX's monitoring events (one listener per process)."""

    n = 0
    _listening = False

    @classmethod
    def start(cls) -> type:
        if not cls._listening:
            from jax import monitoring

            monitoring.register_event_listener(cls._event)
            monitoring.register_event_duration_secs_listener(cls._duration)
            cls._listening = True
        return cls

    @classmethod
    def _event(cls, event, **_):
        cls.n += event == "/jax/compilation_cache/cache_hits"

    @classmethod
    def _duration(cls, event, _secs, **_):
        cls.n += event == "/jax/core/compile/backend_compile_duration"


def _gap_stats(gaps: list) -> dict:
    """Widest and mean gap, and the share of tokens the reference also
    puts first."""
    g = np.concatenate(gaps)
    return {"max": float(g.max()), "mean": float(g.mean()), "zero_share": float(np.mean(g == 0))}


def _ttft_quantiles(rec) -> dict:
    from bench.readers import percentile, ttft_s

    end = max([t for r in rec.records for t in r.tokens] + [rec.window[1]])
    vals = [ttft_s(r, end) for r in rec.requests]
    out = {p: 1e3 * percentile(vals, p) for p in (50, 80, 90)} if vals else {}
    # the median of each third of the window, by due time: a queue that
    # grows through the window shows as a rising median
    w0, w1 = rec.window
    for k in range(3):
        lo, hi = w0 + k * (w1 - w0) / 3, w0 + (k + 1) * (w1 - w0) / 3
        part = [ttft_s(r, end) for r in rec.requests if lo <= r.due < hi]
        out[f"p50_third{k + 1}"] = 1e3 * percentile(part, 50) if part else None
    return out


def choose_sample(records: list, seed: int) -> list:
    """The longest finished request and a seeded draw of others, until the
    sample holds ``CHECK_TOKENS`` served tokens or ``CHECK_REQUESTS``
    requests."""
    done = [r for r in records if r.done is not None and r.served]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.req.prompt) + len(r.served), r.due))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 1])
    sample = [longest]
    for i in rng.permutation(len(rest)):
        if sum(len(r.served) for r in sample) >= CHECK_TOKENS or len(sample) >= CHECK_REQUESTS:
            break
        sample.append(rest[i])
    return sample


def run_cell(name: str, seed: int, seconds: float, trace: bool, t_start: float,
             spec: Spec | None = None, engine_overrides: dict | None = None,
             traffic_overrides: dict | None = None, keep_trace: str | None = None,
             control: str | None = None, require_tpu: bool = True) -> dict:
    """One run of cell ``name``.  The overrides, ``keep_trace`` (copy the
    trace file there) and ``control`` (judge the reference in that lower
    precision, the architecture's ``logit_gaps``, in the program's place) serve
    ``bench/calibrate.py`` and the tests; a benchmark run takes the cell as
    ``BENCHMARK.json`` and its files give it."""
    import jax

    from bench import client as cl
    from bench import traffic, weights
    from bench.trace import load, reduce
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serve import Engine

    spec = spec or Spec()
    cell = spec.cell(name)
    chips = cell["chips"]
    devices = jax.devices()
    dev = devices[0]
    if len(devices) < chips or (require_tpu and dev.platform != "tpu"):
        raise SystemExit(f"bench: {name} needs {chips} TPU chip(s), "
                         f"JAX found {len(devices)} x {dev.platform}")
    place = weights.Place(devices[:chips])
    peak = peaks(dev.device_kind) if require_tpu else None
    enable_compile_cache()
    # cache every program, however quick to compile, so a warm set-up
    # compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = _Compiles.start()

    cfg = spec.config(cell["config"])
    mix_spec = json.loads((spec.traffic_dir / f"{cell['traffic']}.json").read_text())
    mix_spec.update(traffic_overrides or {})
    arch = spec.arch(cfg["architecture"])
    mc = arch.model_config(cfg)
    m = arch.dims(mc)
    sc = serve_config(cfg, **(engine_overrides or {}))
    embed_std = cfg["weights"]["embed_std"]
    lead_in, drain_s = mix_spec["lead_in_s"], mix_spec["drain_s"]

    w = weights.make(arch, m, seed, embed_std, mc.dtype, place)
    eng = Engine(mc, arch.program_tree(w), sc)
    del w
    mix = traffic.Mix(mix_spec, seed, m.vocab)
    for batch in mix.warmup(sc.prefill_buckets, sc.max_batch):
        cl.Client(eng).finish_all(batch)

    client = cl.Client(eng)
    snaps: dict[str, dict] = {}
    # trace: None (off), "pending", "on" or "done"
    state = SimpleNamespace(trace="pending" if trace else None, trace_end=None, ann=None,
                            tmp=None, interval=None, compiles0=None)
    start = cl.clock()
    w0, w1 = start + lead_in, start + lead_in + seconds

    def window_answered() -> bool:
        return all(r.tokens for r in client.records if w0 <= r.due < w1)

    def tick(now: float) -> None:
        if "w0" not in snaps and now >= w0:
            snaps["w0"] = counters(eng)
            state.compiles0 = compiles.n
        if "w1" not in snaps and now >= w1:
            snaps["w1"] = counters(eng)
            state.compiles_in_window = compiles.n - state.compiles0
            state.live_at_close = len(client.live)
        # the traced stretch follows the window, once every request due in
        # it has its first token, so that starting and stopping the profiler
        # (which stalls the client for seconds) touches none of the
        # window's host-clock numbers
        if state.trace == "pending" and now >= w1 and (
                not mix.open or window_answered() or now >= w1 + TRACE_WAIT_S):
            state.tmp = tempfile.TemporaryDirectory(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(state.tmp.name, profiler_options=opts)
            state.ann = jax.profiler.TraceAnnotation("bench.window")
            state.ann.__enter__()
            state.trace, state.trace_end = "on", cl.clock() + TRACE_S
            state.interval = [cl.clock(), None]
            snaps["t0"] = counters(eng)
        if state.trace == "on" and now >= state.trace_end:
            snaps["t1"] = counters(eng)
            state.interval[1] = cl.clock()
            state.ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            state.trace = "done"

    def hold() -> bool:
        """Whether the traffic goes on past the window: until the traced
        stretch is over."""
        return state.trace in ("pending", "on")

    if mix.open:
        sched = mix.schedule(lead_in, seconds, drain_s)
        cl.drive_open(client, sched, start, (w0, w1), drain_s, tick, hold)
    else:
        cl.drive_closed(client, mix.clients(), start, (w0, w1), drain_s, tick, hold)
    tick(max(cl.clock(), w1))
    if state.trace == "on":  # the run ended before the traced stretch did
        tick(math.inf)

    # the fullest chip's peak as JAX reports it; where the runtime reports
    # none, the bytes the process holds on each chip now, a lower bound of
    # its peak
    peak_bytes = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use") or sum(
            s.data.nbytes for x in jax.live_arrays() for s in x.addressable_shards
            if s.device == d)
        for d in devices[:chips])
    in_window = ([r for r in client.records if w0 <= r.due < w1] if mix.open
                 else [r for r in client.records
                       if r.submitted < w1 and (r.done is None or r.done >= w0)])
    failed = sum(r.done is None for r in in_window)
    rec = SimpleNamespace(
        arch=arch, dims=m, peak=peak, chips=chips, decode_steps=sc.decode_steps,
        window=(w0, w1),
        requests=in_window, records=client.records, setup_s=w0 - t_start,
        counters=snaps, open=mix.open, prefix_tokens=(
            mix_spec["shared_prefix"]["tokens"] if mix_spec.get("shared_prefix") else 0),
        trace=None, trace_interval=state.interval,
    )
    if trace:
        files = list(Path(state.tmp.name).rglob("*.xplane.pb"))
        if keep_trace:
            shutil.copy(files[0], keep_trace)
        rec.trace = reduce(load(files[0]))
        state.tmp.cleanup()

    sample = choose_sample(in_window if mix.open else client.records, seed)
    client_records = client.records
    del eng, client, tick
    gc.collect()

    # the check: weights drawn again, the reference over each sampled
    # prompt with its served tokens
    w = weights.make(arch, m, seed, embed_std, mc.dtype, place)
    seqs = [(r.req.prompt, r.served) for r in sample]
    program = arch.logit_gaps(m, w, seqs, sc.max_seq_len, place=place)
    gaps = (arch.logit_gaps(m, w, seqs, sc.max_seq_len, control, place)
            if control else program)
    del w
    gc.collect()
    served = sum(len(r.served) for r in sample)
    # an answer cut short: fewer tokens than asked for, where the sequence
    # cap did not stop it (the traffic sets no end-of-sequence token)
    short = sum(
        len(r.served) < min(r.req.max_new, sc.max_seq_len - len(r.req.prompt) - 1)
        for r in client_records if r.done is not None and r.due >= w0)
    limits = cfg["checks"]
    stats = _gap_stats(gaps) if served else {"max": math.inf, "mean": math.inf}
    checks = {
        "unfinished_requests": {"value": failed, "limit": 0},
        "short_answers": {"value": short, "limit": 0},
        "checked_tokens": {"value": served, "limit": limits["min_checked_tokens"]},
    }
    # the configuration names the gap statistics it is held to
    for key in ("max_logit_gap", "mean_logit_gap"):
        if key in limits:
            checks[key] = {"value": stats[key.split("_")[0]], "limit": limits[key]}
    correct = (failed == 0 and short == 0 and served >= limits["min_checked_tokens"]
               and all(checks[k]["value"] <= limits[k] for k in checks if k.endswith("gap")))

    metrics = {}
    for mdef in spec.metrics(name, trace):
        value = spec.reader(mdef["name"])(rec)
        if value is not None:
            metrics[mdef["name"]] = {"value": float(value), "unit": mdef["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak_bytes)}
    result = {"correct": bool(correct), "attempted": len(in_window), "failed": failed,
              "metrics": metrics, "device": device}
    if rec.trace is not None:
        device["busy_s"] = rec.trace["busy_s"]
        device["window_s"] = rec.trace["window_s"]
        result["breakdown"] = {"device_ops": rec.trace["device_ops"],
                               "idle_gaps": rec.trace["idle_gaps"]}
    result["diagnostics"] = {
        "compiles_in_window": getattr(state, "compiles_in_window", None),
        "live_at_close": getattr(state, "live_at_close", None),
        "setup_s": rec.setup_s,
        "counters": snaps, "checked_requests": len(sample),
        "control": control,
        "ttft_ms": _ttft_quantiles(rec) if mix.open else None,
        # collectives in the traced stretch, seconds per chip: all of them,
        # and the part in which nothing else ran on the chip
        "collectives": {"device_s": rec.trace["collective_s"],
                        "alone_s": rec.trace["collective_alone_s"]} if rec.trace else None,
        "gap_per_request": [float(g.max()) if g.size else 0.0 for g in gaps],
        "gaps": {k: _gap_stats(v) for k, v in (("program", program), ("control", gaps))
                 if served and (k == "program" or control)},
        # share of the checked tokens that repeat the token before them
        "echo_share": float(np.mean([t == s for r in sample
                                     for s, t in zip(r.req.prompt[-1:] + r.served, r.served)]))
        if served else None,
    }
    result["checks"] = checks
    for key, c in checks.items():
        print(f"check {key}: {c['value']} limit {c['limit']}", file=sys.stderr)
    return result
