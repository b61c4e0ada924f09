"""CPU tests of the on-chip benchmark in ``bench/``: the trace reduction on a
trace recorded on the chip, the work counts, the lookup of cells by name,
the refusal to run without a chip, the plain reference against the
engine, and the check's control and planted faults at a tiny size."""

from __future__ import annotations

import json
import math
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, readers, trace, traffic, weights  # noqa: E402
from bench.arch import llama  # noqa: E402

TESTDATA = ROOT / "bench" / "testdata"
SEED = 2**31 + 77  # wider than 32 bits, as the benchmark's seeds may be


def _config(name: str) -> dict:
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


def _small(name: str) -> dict:
    """A configuration file's keys (muP scalings included) at a CPU size."""
    cfg = _config(name)
    heads, kv = (4, 4) if name == "minicpm-2b" else (4, 2)
    cfg["model"].update(hidden_size=64, intermediate_size=96, num_hidden_layers=2,
                        num_attention_heads=heads, num_key_value_heads=kv,
                        vocab_size=200, dim_model_base=16)
    if "dim_model_base" not in _config(name)["model"]:
        del cfg["model"]["dim_model_base"]
    return cfg


# ---------------------------------------------------------------- trace --

TRACE = TESTDATA / "trace-tiny.xplane.pb.gz"
TRACE_EXPECT = TESTDATA / "trace-tiny.expected.json"


@pytest.fixture(scope="module")
def tiny_trace():
    if not TRACE.exists():
        pytest.fail(f"missing {TRACE}")
    return trace.load(TRACE)


def test_trace_reduction_matches_a_brute_force_count(tiny_trace):
    """Busy time equals a sweep that counts the operations running at each
    boundary, program times equal plain sums over the module events."""
    out = trace.reduce(tiny_trace)
    lo, hi = next((s, e) for s, e, n in tiny_trace["host"] if n == trace.WINDOW)
    (ops,) = tiny_trace["ops"].values()
    edges = sorted([(max(s, lo), 1) for s, e, _ in ops if e > lo and s < hi]
                   + [(min(e, hi), -1) for s, e, _ in ops if e > lo and s < hi])
    busy, running, last = 0.0, 0, lo
    for t, step in edges:
        if running > 0:
            busy += t - last
        running, last = running + step, t
    assert running == 0
    assert out["busy_s"] == pytest.approx(busy / 1e9, rel=1e-9)
    assert 0 < out["busy_s"] < out["window_s"] == pytest.approx((hi - lo) / 1e9)
    (mods,) = tiny_trace["modules"].values()
    for prog, got in out["programs"].items():
        evs = [(s, e) for s, e, n in mods if trace.program_of(n) == prog and e > lo and s < hi]
        assert got["count"] == len(evs)
        assert got["device_s"] == pytest.approx(
            sum(min(e, hi) - max(s, lo) for s, e in evs) / 1e9)
    assert "decode" in out["programs"]
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
    gap_total = sum(g for _, g in out["idle_gaps"])
    assert gap_total <= out["window_s"] - out["busy_s"] + 1e-9


def test_trace_reduction_gives_the_recorded_numbers(tiny_trace):
    """The numbers read off this trace when it was recorded on the chip."""
    want = json.loads(TRACE_EXPECT.read_text())
    out = trace.reduce(tiny_trace)
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert out["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    for prog, p in want["programs"].items():
        assert out["programs"][prog]["count"] == p["count"]
        assert out["programs"][prog]["device_s"] == pytest.approx(p["device_s"], rel=1e-9)


def _fixture_rec(reduced: dict, chips: int) -> SimpleNamespace:
    """A run record over the fixture's reduced trace: the tiny config, two
    requests whose decode tokens and one first token fall in the stretch."""
    from bench.client import Record
    from bench.traffic import Req

    cfg = json.loads((TESTDATA / "configs" / "tiny.json").read_text())
    recs = []
    for i, n in enumerate((10, 20)):
        r = Record(Req([1] * n, 16, due=0.0), 0.0)
        r.tokens = [0.5 + i + 0.1 * k for k in range(16)]
        recs.append(r)
    counters = {k: {c: 0 for c in harness.COUNTERS} for k in ("t0", "t1")}
    return SimpleNamespace(
        arch=llama, dims=llama.dims(harness.model_config(cfg)), peak=harness.peaks("TPU v5 lite"),
        chips=chips, decode_steps=4, trace=reduced, trace_interval=(1.0, 3.0), records=recs,
        counters=counters, prefix_tokens=0)


def test_shares_of_a_peak_are_taken_over_the_cells_chips(tiny_trace):
    """On the recorded trace: with one chip the roofline and MFU shares are
    the work's least time at one chip's peaks over the recorded device
    times; a cell of four chips reads a quarter of each."""
    want = json.loads(TRACE_EXPECT.read_text())
    reduced = trace.reduce(tiny_trace)
    one, four = _fixture_rec(reduced, 1), _fixture_rec(reduced, 4)
    flops, nbytes = readers.decode_work(one)
    least = max(flops / 197e12, nbytes / 819e9)
    assert readers.decode_roofline(one) == pytest.approx(
        100 * least / want["programs"]["decode"]["device_s"], rel=1e-9)
    pflops = readers.prefill_work(one)[0]
    assert readers.mfu(one) == pytest.approx(
        100 * (flops + pflops) / (want["window_s"] * 197e12), rel=1e-9)
    spec = harness.Spec()
    for name in ("decode_roofline.tput", "prefill_roofline.lat", "mfu.tput"):
        a, b = spec.reader(name)(one), spec.reader(name)(four)
        assert 0 < a < 100 and b == pytest.approx(a / 4, rel=1e-12), name
    assert spec.reader("decode_step_ms.tput")(four) == spec.reader("decode_step_ms.tput")(one)


def _op(start, end, name):
    return (start * 1000, end * 1000, f"%{name} = f32[8]{{0}} {name.split('.')[0]}(...)")


def test_collective_share_on_a_synthetic_op_list():
    """Collectives counted by op name inside the engine's programs only,
    averaged over the chips, over the programs' device time; the part
    with no other op running excludes the loop that holds them."""
    plane = [
        _op(0, 100, "while.41"),                  # the scan's loop holds all below
        _op(0, 20, "fusion.1"),
        _op(20, 30, "all-gather.151"),            # alone: 10
        _op(30, 40, "async-collective-start"),    # under a fusion from 35: 5 alone
        _op(35, 50, "fusion.266"),
        _op(50, 60, "all-reduce.9"),              # alone: 10
        _op(60, 70, "async-collective-done.1"),   # alone: 10
        _op(70, 100, "fusion.2"),
        _op(110, 120, "all-to-all.7"),            # in no engine program: not counted
    ]
    idle = [_op(0, 100, "while.41"), _op(0, 100, "fusion.3")]
    events = {
        "ops": {"/device:TPU:0": plane, "/device:TPU:1": idle},
        "modules": {"/device:TPU:0": [(0, 100_000, "jit__decode_scan(1)"),
                                      (110_000, 120_000, "jit_broadcast(2)")],
                    "/device:TPU:1": [(0, 100_000, "jit__decode_scan(1)")]},
        "host": [(0, 200_000, trace.WINDOW)],
    }
    out = trace.reduce(events)
    assert out["collective_s"] == pytest.approx(40e-6 / 2)
    assert out["collective_alone_s"] == pytest.approx(35e-6 / 2)
    assert out["programs"]["decode"]["device_s"] == pytest.approx(100e-6)
    rec = SimpleNamespace(trace=out)
    read = harness.Spec().reader("collective_share.mesh")
    assert read(rec) == pytest.approx(20.0)
    assert read(SimpleNamespace(trace=dict(out, collective_s=0.0))) is None
    assert read(SimpleNamespace(trace=None)) is None


# ----------------------------------------------------------------- work --

@pytest.mark.parametrize("name,per_layer,vocab_d", [
    # d*hd*(2H + 2Hkv) + 3*d*ff, by hand
    ("minicpm-2b", 2304 * 64 * (72 + 72) + 3 * 2304 * 5760, 122753 * 2304),
    ("granite-8b-l18", 4096 * 128 * (64 + 16) + 3 * 4096 * 14336, 49152 * 4096),
])
def test_work_counts_match_hand_counts(name, per_layer, vocab_d):
    m = llama.dims(harness.model_config(_config(name)))
    assert llama.linear_params(m) == m.layers * per_layer
    norms = (2 * m.layers + 1) * m.d_model
    assert llama.weight_bytes(m) == 2 * (m.layers * per_layer + vocab_d + norms)
    kv = {"minicpm-2b": 2 * 2 * 40 * 36 * 64, "granite-8b-l18": 2 * 2 * 18 * 8 * 128}[name]
    assert llama.kv_bytes_per_pos(m) == kv
    # two decode steps making tokens at positions 10 and 20
    flops, nbytes = llama.decode_work(m, [10, 20], steps=2)
    att = 4 * m.layers * m.heads * m.head_dim * (11 + 21)
    assert flops == 2 * (2 * m.layers * per_layer) + att + 2 * 2 * m.d_model * m.vocab
    assert nbytes == 2 * llama.weight_bytes(m) + kv * 32
    # one prompt of 5 new tokens: 1 + 2 + ... + 5 keys
    flops, nbytes = llama.prefill_work(m, 5, [5], dispatches=1)
    assert flops == 5 * 2 * m.layers * per_layer + 4 * m.layers * m.heads * m.head_dim * 15 \
        + 2 * m.d_model * m.vocab
    rec = SimpleNamespace(peak=harness.peaks("TPU v5 lite"), chips=1)
    assert readers.least_seconds(rec, flops, nbytes) == max(
        flops / 197e12, nbytes / 819e9)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        harness.peaks("TPU v9 imaginary")


# ---------------------------------------------------------- lookup --

def test_cells_configs_and_metrics_are_found_by_name():
    spec = harness.Spec()
    for cell in spec.data["workloads"]:
        cfg = spec.config(cell["config"])
        assert (spec.traffic_dir / f"{cell['traffic']}.json").exists()
        harness.model_config(cfg)
        for traced in (False, True):
            names = [m["name"] for m in spec.metrics(cell["name"], traced)]
            assert names, (cell["name"], traced)
            for n in names:
                assert callable(spec.reader(n))
        e2e = {m["name"] for m in spec.metrics(cell["name"], False)}
        assert "setup_s" in e2e and len(e2e) >= 2


@pytest.mark.parametrize("root", [ROOT / "bench", TESTDATA])
def test_every_configuration_names_an_architecture_that_loads(root):
    spec = harness.Spec(root.parent if root.name == "bench" else root, root)
    for c in spec.data["configs"]:
        cfg = spec.config(c["name"])
        mod = spec.arch(cfg["architecture"])
        for fn in ("model_config", "dims", "draw", "program_tree", "logit_gaps",
                   "decode_work", "prefill_work"):
            assert callable(getattr(mod, fn)), (cfg["architecture"], fn)
        m = mod.dims(mod.model_config(cfg))
        assert set(mod.SPLIT_AXIS) == set(jax.eval_shape(
            lambda k: mod.draw(m, k, 0.02, "bfloat16"), jax.random.PRNGKey(0)))
    with pytest.raises(KeyError, match="no architecture"):
        spec.arch("no-such-architecture")
    with pytest.raises(KeyError, match="no architecture"):
        harness.model_config(dict(cfg, architecture="no-such-architecture"))


def test_a_cell_added_as_files_and_entries_is_found(tmp_path):
    """A new configuration of a new architecture, mix and cell: the
    architecture's module, data files and BENCHMARK.json entries only, no
    edit of a file the benchmark has."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for d in ("configs", "traffic", "arch"):
        (tmp_path / d).mkdir()
    # an architecture of its own: llama's functions with its own work count
    (tmp_path / "arch" / "probe.py").write_text(
        "from bench.arch.llama import *  # noqa: F403\n"
        "from bench.arch.llama import logit_gaps, model_config  # noqa: F401\n\n\n"
        "def decode_work(m, positions, steps):\n"
        "    return 1e12, 0.0\n")
    cfg = _small("granite-8b-l18")
    cfg.update(name="granite-small", architecture="probe")
    (tmp_path / "configs" / "granite-small.json").write_text(json.dumps(cfg))
    mix = json.loads((ROOT / "bench" / "traffic" / "agent-32c.json").read_text())
    (tmp_path / "traffic" / "burst.json").write_text(json.dumps(dict(mix, clients=3)))
    bench["configs"].append({"name": "granite-small", "source": "test",
                             "file": "configs/granite-small.json", "reduced": [], "why": "t"})
    bench["workloads"].append({"name": "granite-small.burst", "config": "granite-small",
                               "traffic": "burst", "chips": 1, "why": "t"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "granite8b-decode" in m.get("workloads", []):
            m["workloads"].append("granite-small.burst")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    spec = harness.Spec(tmp_path, tmp_path)
    assert spec.cell("granite-small.burst")["traffic"] == "burst"
    assert spec.config("granite-small")["model"]["hidden_size"] == 64
    probe = spec.arch(spec.config("granite-small")["architecture"])
    assert probe is not llama and probe.logit_gaps is llama.logit_gaps
    assert probe.model_config(cfg).n_layers == 2
    rec = SimpleNamespace(arch=probe, dims=probe.dims(probe.model_config(cfg)),
                          peak={"bf16_flops_per_s": 1e14, "hbm_bytes_per_s": 1e12}, chips=1,
                          decode_steps=4, trace={"programs": {"decode": {"count": 1,
                                                                         "device_s": 0.02}}},
                          trace_interval=(0.0, 1.0), records=[])
    assert readers.decode_roofline(rec) == pytest.approx(50.0)
    got = {m["name"] for m in spec.metrics("granite-small.burst", False)}
    assert got == {"output_tokens_per_s", "setup_s"}
    got = {m["name"] for m in spec.metrics("granite-small.burst", True)}
    assert {"decode_roofline.tput", "mfu.tput", "decode_step_ms.tput"} <= got
    with pytest.raises(KeyError):
        spec.cell("no-such-cell")


def test_the_command_refuses_a_machine_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "minicpm2b-chat", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.lstrip().startswith("{")]


def test_the_command_refuses_a_checkout_without_the_program(tmp_path):
    """Only BENCHMARK.json and the benchmark's own files: no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("testdata", "__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "minicpm2b-chat", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.lstrip().startswith("{")]


# ---------------------------------------------------------- traffic --

@pytest.mark.parametrize("mix", ["chat", "agent-32c"])
def test_every_seed_gets_the_same_work_in_another_order(mix):
    spec = json.loads((ROOT / "bench" / "traffic" / f"{mix}.json").read_text())
    spec = dict(spec, rate_rps=spec.get("rate_rps", 1.0))

    def sizes(seed):
        mx = traffic.Mix(spec, seed, 1000)
        reqs = mx.schedule(5, 20, 5) if mx.open else sum(mx.clients(), [])
        return reqs, sorted((len(r.prompt), r.max_new, r.tenant) for r in reqs)

    a, sa = sizes(SEED)
    b, sb = sizes(SEED + 1)
    c, _ = sizes(SEED)
    assert sa == sb
    assert [r.prompt for r in a] == [r.prompt for r in c]
    assert [r.prompt for r in a] != [r.prompt for r in b]
    if spec["loop"] == "open":
        window = [r.due for r in a if 5 <= r.due < 25]
        assert len(window) == math.ceil(spec["rate_rps"] * 20)
        assert min(window) == pytest.approx(5.0)


# -------------------------------------------------- reference vs engine --

@pytest.mark.parametrize("name", ["minicpm-2b", "granite-8b-l18"])
def test_reference_matches_engine_prefill_and_cached_decode(name):
    from repro.configs.base import ServeConfig
    from repro.models import lm
    from repro.serve import Engine

    cfg = _small(name)
    mc = harness.model_config(cfg)
    m = llama.dims(mc)
    w = weights.make(llama, m, SEED, 0.02)
    eng = Engine(mc, llama.program_tree(w),
                 ServeConfig(max_batch=2, max_seq_len=32, prefill_buckets=(32,)))
    ex = eng.executor
    rng = np.random.default_rng(0)
    lens = np.array([11, 23], np.int32)
    toks = np.zeros((2, 32), np.int32)
    for r, n in enumerate(lens):
        toks[r, :n] = rng.integers(0, m.vocab, n)
    last, caches = jax.jit(ex._prefill_batch)(
        ex.params, jnp.asarray(toks), jnp.asarray(lens), ex.caches,
        jnp.arange(2, dtype=jnp.int32), jnp.zeros(2, jnp.int32))
    ref = llama.logits(m, w, toks)
    scale = np.abs(ref).max()
    for r, n in enumerate(lens):
        got = np.asarray(last[r, :m.vocab], np.float32)
        assert np.abs(got - ref[r, n - 1]).max() < 0.03 * scale
    nxt = rng.integers(0, m.vocab, 2).astype(np.int32)
    dec, _ = lm.decode_step(ex.params, ex.cfg, jnp.asarray(nxt)[:, None], jnp.asarray(lens),
                            caches, kernel=ex.kernel)
    toks2 = toks.copy()
    toks2[np.arange(2), lens] = nxt
    ref2 = llama.logits(m, w, toks2)
    for r, n in enumerate(lens):
        got = np.asarray(dec[r, :m.vocab], np.float32)
        assert np.abs(got - ref2[r, n]).max() < 0.03 * scale


# ------------------------------------------------ the check and its faults --

def _tiny_run(cell, **kw):
    spec = harness.Spec(TESTDATA, TESTDATA)
    return harness.run_cell(cell, SEED, 2.0, False, time.perf_counter(), spec=spec,
                            require_tpu=False, **kw)


@pytest.fixture(scope="module")
def tiny_limit():
    return json.loads((TESTDATA / "configs" / "tiny.json").read_text())["checks"]


def test_sound_run_is_correct_and_the_lower_precision_control_is_not(tiny_limit):
    sound = _tiny_run("tiny-chat")
    assert sound["correct"], sound["checks"]
    control = _tiny_run("tiny-chat", control="w8a8")
    assert not control["correct"], control["checks"]
    assert list(control)[-1] == "checks"


def _alter_token(monkeypatch):
    """A token altered where it is produced: position 20's greedy choice."""
    from repro.serve import executor

    real = executor.sample_tokens

    def altered(logits, key, **kw):
        tok = real(logits, key, **kw)
        return jnp.where(kw["positions"] == 20, (tok + 1) % 128, tok)

    monkeypatch.setattr(executor, "sample_tokens", altered)


def _stale_state(monkeypatch):
    """A step that returns its state unchanged: decode writes no KV."""
    from repro.serve import kv_cache

    monkeypatch.setattr(kv_cache, "paged_decode_write", lambda cache, upd, pos: cache)


def _half_batch(monkeypatch):
    """Half of the batch left out: odd slots never decode."""
    from repro.serve.executor import ModelExecutor

    real = ModelExecutor._decode_scan

    def half(self, params, tokens, positions, active, *rest):
        keep = jnp.arange(active.shape[0]) % 2 == 0
        return real(self, params, tokens, positions, active & keep, *rest)

    monkeypatch.setattr(ModelExecutor, "_decode_scan", half)


def test_a_traced_run_records_after_the_window(monkeypatch):
    """The profiler starts once the window has closed and its requests have
    their first tokens, records while the traffic goes on, and leaves the
    window's host-clock metrics to an untraced stretch."""
    from bench import client as cl

    events, submits = [], []

    def start(logdir, **_):
        Path(logdir, "t.xplane.pb").write_bytes(b"")
        events.append(cl.clock())

    real_submit = cl.Client.submit

    def submit(self, req, due):
        submits.append(cl.clock())
        return real_submit(self, req, due)

    monkeypatch.setattr(harness, "TRACE_S", 1.0)
    monkeypatch.setattr(jax.profiler, "start_trace", start)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: events.append(cl.clock()))
    monkeypatch.setattr(cl.Client, "submit", submit)
    monkeypatch.setattr(trace, "load", lambda path: {})
    monkeypatch.setattr(trace, "reduce", lambda ev: {
        "window_s": 1.0, "busy_s": 0.5, "programs": {}, "collective_s": 0.0,
        "collective_alone_s": 0.0, "device_ops": [], "idle_gaps": []})
    # a CPU run has no chip and so no peak to share against
    monkeypatch.setattr(readers, "mfu", lambda rec: None)
    t_start = time.perf_counter()
    out = harness.run_cell("tiny-chat", SEED, 2.0, True, t_start,
                           spec=harness.Spec(TESTDATA, TESTDATA), require_tpu=False)
    w1 = t_start + out["diagnostics"]["setup_s"] + 2.0
    assert out["correct"], out["checks"]
    t0, t1 = events
    assert w1 <= t0 and t1 - t0 >= 1.0
    assert any(t0 <= s < t1 for s in submits)
    assert {"ttft_p90_ms", "gen_lag_p90_ms", "queue_wait_p90_ms"} <= set(out["metrics"])
    assert out["device"]["window_s"] == 1.0


@pytest.mark.parametrize("fault", [_alter_token, _stale_state, _half_batch])
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    out = _tiny_run("tiny-agent", traffic_overrides={"drain_s": 3})
    assert not out["correct"], out["checks"]


# -------------------------------------------------------------- readers --

def test_readers_on_a_synthetic_record():
    from bench.client import Record
    from bench.traffic import Req

    m = llama.dims(harness.model_config(_config("granite-8b-l18")))
    recs = []
    for i in range(10):
        r = Record(Req([1] * 100, 8, due=10.0 + i), 10.0 + i, submitted=10.0 + i + 0.001,
                   created=10.0 + i + 0.001, admitted=10.0 + i + 0.011)
        r.tokens = [10.0 + i + 0.2 + 0.05 * k for k in range(8)]
        r.done = r.tokens[-1]
        recs.append(r)
    rec = SimpleNamespace(
        arch=llama, dims=m, peak=harness.peaks("TPU v5 lite"), chips=1, decode_steps=4,
        window=(10.0, 20.0),
        requests=recs, records=recs, setup_s=12.5, open=True, prefix_tokens=0,
        counters={k: {c: 0 for c in harness.COUNTERS} for k in ("w0", "w1", "t0", "t1")},
        trace_interval=(10.0, 20.0),
        trace={"window_s": 10.0, "busy_s": 6.0, "programs": {
            "decode": {"count": 20, "device_s": 1.6}, "prefill": {"count": 10, "device_s": 2.0}}},
    )
    rec.counters["t1"]["prompts_admitted"] = 10
    spec = harness.Spec()
    read = {n: spec.reader(n)(rec) for n in (
        "ttft_p90_ms", "itl_p99_ms", "output_tokens_per_s", "setup_s", "gen_lag_p90_ms",
        "queue_wait_p90_ms", "prefill_ms_per_req", "decode_step_ms.tput",
        "device_idle_share.lat", "decode_roofline.lat", "prefill_roofline.lat", "mfu.tput")}
    assert read["ttft_p90_ms"] == pytest.approx(200.0)
    assert read["itl_p99_ms"] == pytest.approx(50.0)
    assert read["output_tokens_per_s"] == pytest.approx(8.0)
    assert read["setup_s"] == 12.5
    assert read["gen_lag_p90_ms"] == pytest.approx(1.0)
    assert read["queue_wait_p90_ms"] == pytest.approx(10.0)
    assert read["prefill_ms_per_req"] == pytest.approx(200.0)
    assert read["decode_step_ms.tput"] == pytest.approx(20.0)
    assert read["device_idle_share.lat"] == pytest.approx(40.0)
    for k in ("decode_roofline.lat", "prefill_roofline.lat", "mfu.tput"):
        assert 0 < read[k] < 100
    assert readers.percentile([3, 1, 2], 50) == 2
    assert readers.percentile([], 90) is None
