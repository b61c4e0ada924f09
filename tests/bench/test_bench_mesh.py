"""CPU tests of the benchmark on a cell that spans several chips, on four
host devices in a child process (``--xla_force_host_platform_device_count``
must be set before JAX starts): the split draw and the reference over the
mesh against the one-device ones, and a four-chip cell served through the
program's mesh path and checked."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
ENV = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=f"{ROOT}{os.pathsep}{ROOT / 'src'}",
           XLA_FLAGS="--xla_force_host_platform_device_count=4")
SEED = 2**31 + 77


def _run(script: str) -> dict:
    """Run ``script`` on four host devices; it prints one JSON line last."""
    out = subprocess.run([sys.executable, "-c", script], cwd=ROOT, env=ENV,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_split_draw_and_reference_equal_the_one_device_ones():
    """Each weight drawn split over four devices holds a quarter of the leaf
    on each and equals the one-device draw bit for bit; the reference run
    layer by layer over the four devices gives the one-device logits bit
    for bit, and so do the check's gaps, the control's too."""
    got = _run(f"""
import json
import jax, numpy as np
from bench import harness, weights
from bench.arch import llama
cfg = json.loads(open("bench/testdata/configs/tiny.json").read())
m = llama.dims(harness.model_config(cfg))
four = weights.Place(jax.devices())
one = weights.make(llama, m, {SEED}, 0.02)
split = weights.make(llama, m, {SEED}, 0.02, place=four)
same = {{k: bool(np.array_equal(np.asarray(one[k]), np.asarray(split[k]))) for k in one}}
share = {{k: [s.data.size * 4 == a.size for s in a.addressable_shards] for k, a in split.items()}}
on_one = {{k: str(next(iter(a.devices()))) for k, a in one.items() if len(a.devices()) != 1}}
rng = np.random.default_rng(0)
toks = rng.integers(0, m.vocab, (3, 24)).astype(np.int32)
ref_one = llama.logits(m, one, toks)
ref_four = llama.logits(m, split, toks, place=four)
seqs = [(list(toks[r, :8]), list(toks[r, 8:20])) for r in range(3)]
gaps = [bool(np.array_equal(a, b)) for q in (None, "w8a8") for a, b in zip(
    llama.logit_gaps(m, one, seqs, 32, q), llama.logit_gaps(m, split, seqs, 32, q, four))]
print(json.dumps({{"same": same, "share": share, "on_one": on_one, "gaps": gaps,
                  "logits": bool(np.array_equal(ref_one, ref_four)), "devices": len(jax.devices())}}))
""")
    assert got["devices"] == 4
    assert got["same"] and all(got["same"].values()), got["same"]
    assert all(all(s) for s in got["share"].values()), got["share"]
    assert got["on_one"] == {}
    assert got["logits"]
    assert got["gaps"] == [True] * 6


def test_a_four_chip_cell_is_correct_and_its_control_is_not():
    """A tiny cell with ``chips: 4`` and the program's ``shard_decode``
    through ``run_cell`` on four host devices: its weights drawn split, the
    engine on the program's mesh, the reference over the cell's devices.
    The sound run is correct; the lower-precision control in the program's
    place is not."""
    got = _run(f"""
import json, time
from bench import harness
spec = harness.Spec(harness.ROOT / "bench" / "testdata", harness.ROOT / "bench" / "testdata")
runs = [harness.run_cell("tiny-chat-4chip", {SEED}, 2.0, False, time.perf_counter(), spec=spec,
                         require_tpu=False, control=c) for c in (None, "w8a8")]
print(json.dumps([{{"correct": r["correct"], "checks": r["checks"], "device": r["device"],
                   "metrics": sorted(r["metrics"])}} for r in runs]))
""")
    sound, control = got
    assert sound["correct"], sound["checks"]
    assert not control["correct"], control["checks"]
    assert sound["device"]["count"] == 4 and sound["device"]["memory_peak_bytes"] > 0
    assert sound["metrics"] == ["itl_p99_ms", "setup_s", "ttft_p50_ms"]
