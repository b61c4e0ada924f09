"""CPU tests of what a profiler trace holds of the engine: its ``serve.*``
spans nested in ``serve.step`` with the program each serves, the idle
device time put down to the innermost host span (``bench/spans.py``), and
the XLA module names that ``bench/trace.py`` finds the programs by."""

from __future__ import annotations

import gzip
import json
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, spans, trace  # noqa: E402
from repro import configs  # noqa: E402
from repro.configs.base import ServeConfig  # noqa: E402
from repro.core import precision as P  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.serve import Engine  # noqa: E402

TESTDATA = ROOT / "bench" / "testdata"
#: an int8 KV cache: prompt tails replay through the extend program
KV8 = P.PrecisionPolicy("kv8", (P.Rule("kv_cache", P.int8(per_channel=False)),))


@pytest.fixture(scope="module")
def model():
    cfg = configs.get_config("granite-8b", reduced=True)
    return cfg, lm.init_params(cfg, jax.random.PRNGKey(5))


def _engine(model, **kw):
    base = dict(max_batch=2, max_seq_len=64, prefill_buckets=(8, 16, 32),
                decode_steps=2, temperature=0.0, kv_layout="paged", kv_page_size=8,
                policy=KV8, prefill_chunk=8)
    base.update(kw)
    return Engine(*model, ServeConfig(**base))


def _prompts(n=3, length=20):
    rng = np.random.default_rng(0)
    return [list(rng.integers(1, 200, length - i)) for i in range(n)]


# ---------------------------------------------------------------- spans --

@pytest.mark.parametrize("kw,device", [
    (dict(async_loop=True), False),                      # untraced: NULL_TRACER
    (dict(trace_phases=True, phase_mode="fenced"), True),
])
def test_engine_spans_reach_a_profiler_trace(model, kw, device, tmp_path):
    eng = _engine(model, **kw)
    eng.generate(_prompts(), max_new_tokens=5)  # compiles outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.generate(_prompts(), max_new_tokens=5)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    got = spans.engine_spans(path)
    steps = [(s, e) for s, e, n, _ in got if n == "serve.step"]
    inner = [(s, e, n, p) for s, e, n, p in got if n != "serve.step"]
    assert steps and inner
    names = {n for _, _, n, _ in inner}
    want = {"serve.schedule", "serve.host_prep", "serve.dispatch", "serve.sample",
            "serve.collect"}
    assert want | ({"serve.device"} if device else set()) == names
    for s, e, n, prog in inner:
        assert any(a <= s and e <= b for a, b in steps), n
        assert prog is None if n == "serve.schedule" else prog in (
            "prefill", "extend", "decode"), (n, prog)
    assert {p for *_, p in inner} >= {"prefill", "extend", "decode"}
    # the benchmark's loader keeps them beside its own spans, named with
    # their program if asked
    assert len(spans.load(path)["host"]) == len(got)
    tagged = {n for *_, n in spans.load(path, programs=True)["host"]}
    assert {"serve.step", "serve.schedule", "serve.sample[prefill]",
            "serve.host_prep[extend]", "serve.collect[decode]"} <= tagged


# ------------------------------------------------------------ idle gaps --

def _events(ops, host):
    return {"ops": ops, "modules": {}, "host": [(0, 100, trace.WINDOW)] + host}


HOST = [(0, 60, "bench.step"), (5, 35, "serve.step"), (21, 33, "serve.collect"),
        (80, 95, "bench.wait_arrival")]


def test_an_idle_gap_goes_to_the_innermost_span_covering_it():
    host = _events({}, HOST)["host"]
    assert trace._label(host, 8) == "serve.step"
    assert trace._label(host, 30) == "serve.collect"
    assert trace._label(host, 45) == "bench.step"
    assert trace._label(host, 85) == "bench.wait_arrival"
    assert trace._label(host, 75) == "none"
    # device busy 10-20 and 40-50: idle 0-10, 20-40 and 50-100
    events = _events({"/device:TPU:0": [(10, 20, "a"), (40, 50, "b")]}, HOST)
    out = trace.reduce(events)
    assert out["idle_gaps"] == [["none", 50e-9], ["serve.collect", 20e-9],
                                ["serve.step", 10e-9]]
    by = spans.idle_by_span(events)
    assert by == pytest.approx({"none": 50e-9, "serve.collect": 20e-9,
                                "serve.step": 10e-9}, rel=1e-12)
    assert sum(by.values()) == pytest.approx(out["window_s"] - out["busy_s"])
    assert spans.idle_in_engine(by, out["window_s"]) == pytest.approx(30.0)


def test_idle_by_span_averages_over_the_chips_traced():
    events = _events({"/device:TPU:0": [(10, 20, "a"), (40, 50, "b")],
                      "/device:TPU:1": [(0, 100, "c")]}, HOST)
    out = trace.reduce(events)
    by = spans.idle_by_span(events)
    assert sum(by.values()) == pytest.approx(out["window_s"] - out["busy_s"])
    assert by["none"] == pytest.approx(25e-9)


def test_the_recorded_trace_reduces_as_before():
    """``trace-tiny`` has no engine spans: read with them, it gives the
    recorded numbers, and its idle seconds all fall outside them."""
    path = TESTDATA / "trace-tiny.xplane.pb.gz"
    want = json.loads((TESTDATA / "trace-tiny.expected.json").read_text())
    events = spans.load(path)
    assert events == trace.load(path)
    out = trace.reduce(events)
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert out["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    by = spans.idle_by_span(events)
    assert not any(n.startswith(spans.ENGINE) for n in by)
    assert sum(by.values()) == pytest.approx(out["window_s"] - out["busy_s"], rel=1e-9)


def test_useful_share_reads_the_rise_between_two_snapshots():
    snaps = {"w0": {"live": 10, "read": 100}, "w1": {"live": 40, "read": 220}}
    assert spans.useful_share(snaps, "live", "read") == pytest.approx(25.0)
    assert spans.useful_share({"w0": snaps["w0"], "w1": snaps["w0"]}, "live", "read") is None


def test_the_command_reports_the_idle_split_and_the_shares(monkeypatch, capsys):
    """``bench/spans.py`` reads the waste counters at the window's and the
    trace's edges and the kept trace's idle time by span (here the
    recorded ``trace-tiny``, with a stand-in for the run)."""
    runs = []

    def run_cell(name, seed, seconds, traced, t_start, keep_trace=None):
        runs.append((name, seed, seconds, traced, harness.COUNTERS))
        trace_gz = TESTDATA / "trace-tiny.xplane.pb.gz"
        Path(keep_trace).write_bytes(gzip.decompress(trace_gz.read_bytes()))
        snaps = {k: dict.fromkeys(harness.COUNTERS, 0) for k in ("w0", "w1", "t0", "t1")}
        snaps["w1"].update(decode_kv_positions_read=400, decode_kv_positions_live=100,
                           prompt_positions_dispatched=64, prompt_positions_written=16,
                           tokens_generated=250)
        snaps["t1"].update(tokens_generated=51)
        return {"correct": True, "diagnostics": {"counters": snaps}}

    monkeypatch.setattr(harness, "COUNTERS", harness.COUNTERS)  # restored after
    monkeypatch.setattr(harness, "run_cell", run_cell)
    monkeypatch.setattr(sys, "argv", ["spans.py", "--workload", "tiny-agent",
                                      "--seed", str(2**31 + 5), "--seconds", "5"])
    assert spans.main() == 0
    ((name, seed, seconds, traced, counters),) = runs
    assert (name, seed, seconds, traced) == ("tiny-agent", 2**31 + 5, 5.0, True)
    assert set(spans.WASTE) < set(counters)
    out = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert out["decode_kv_useful_share"] == pytest.approx(25.0)
    assert out["prefill_useful_share"] == pytest.approx(25.0)
    assert out["tokens_per_s"]["window"] == pytest.approx(50.0)
    assert out["tokens_per_s"]["traced"] == pytest.approx(51 / out["window_s"])
    assert sum(out["idle_by_span"].values()) == pytest.approx(out["idle_s"])
    assert out["idle_by_span_program"] == out["idle_by_span"]  # no engine spans there
    assert out["device_idle_in_engine"] == 0.0  # the recording predates the spans


# -------------------------------------------------------- program names --

def test_program_module_names_are_those_the_trace_reads(model):
    """The engine's prefill, extend and decode programs compile to XLA
    modules whose names hold the fragment ``bench/trace.py`` maps to each:
    a rename would leave the per-program metrics nothing to read."""
    eng = _engine(model)
    ex = eng.executor
    eng.generate(_prompts(1), max_new_tokens=3)  # makes the bucket-8 program
    calls = {}

    def capture(prog, fn):
        def wrapped(*args):
            calls.setdefault(prog, (fn, args))
            return fn(*args)
        return wrapped

    ex._prefill_fn[8] = capture("prefill", ex._prefill_fn[8])
    ex._extend_fn = capture("extend", ex._extend_fn)
    ex._decode_fn = capture("decode", ex._decode_fn)
    eng.generate(_prompts(1), max_new_tokens=3)
    assert set(calls) == {"prefill", "extend", "decode"}
    for prog, (fn, args) in calls.items():
        hlo = fn.lower(*args).compile().as_text()
        module = hlo.split(None, 2)[1].rstrip(",")  # "HloModule <name>, ..."
        assert trace.program_of(module) == prog, module
