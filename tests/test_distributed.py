"""Distribution layer: sharding rules (single-device), multi-device
collectives + dry-run (subprocess with forced host device count)."""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.launch.mesh import make_mesh
from repro.configs.base import ParallelismConfig
from repro.distributed.sharding import ShardingRules

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))


@pytest.fixture(scope="module")
def rules():
    mesh = make_mesh((1, 1), ("data", "model"))
    return ShardingRules(mesh=mesh, plan=ParallelismConfig())


def test_spec_for_divisibility_fallback(rules):
    # heads=40 doesn't divide model=1? (1 divides everything) — use a fake
    # mesh-shape check through the public API instead:
    spec = rules.spec_for(("embed", "heads"), (64, 40))
    assert isinstance(spec, P)


def test_spec_rank_matches():
    mesh = make_mesh((1, 1), ("data", "model"))
    r = ShardingRules(mesh=mesh)
    spec = r.spec_for(("layers", "embed", "mlp"), (4, 32, 64))
    assert len(spec) == 3


def test_param_tree_shardings_cover_all_leaves(rules):
    from repro.models import lm
    from repro.models import params as params_lib

    cfg = configs.get_config("granite-8b", reduced=True)
    spec = lm.param_spec(cfg)
    sh = rules.tree_shardings(
        params_lib.abstract_params(spec), params_lib.logical_axes(spec)
    )
    n_params = len(jax.tree.leaves(params_lib.abstract_params(spec)))
    n_shardings = len(jax.tree.leaves(sh, is_leaf=lambda x: hasattr(x, "spec")))
    assert n_params == n_shardings


def _run(script: str, devices: int = 8) -> str:
    code = (
        "import os\n"
        f"os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count={devices}'\n"
        + script
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=ENV, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_ring_collective_matmul_multidevice():
    out = _run(
        """
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.collectives import ring_collective_matmul
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
w = jnp.asarray(rng.normal(size=(32, 24)), jnp.float32)
with mesh:
    out = ring_collective_matmul(mesh, x, w, axis="model")
err = float(jnp.max(jnp.abs(out - x @ w)))
print("ERR", err)
assert err < 1e-4
"""
    )
    assert "ERR" in out


def test_compressed_allreduce_error_feedback_converges():
    out = _run(
        """
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.collectives import make_compressed_grad_allreduce, init_error_buffers
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("data",))
g = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(32, 32)), jnp.float32)}
err = init_error_buffers(g)
f = make_compressed_grad_allreduce(mesh, axis_name="data")
# accumulate compressed means over steps: error feedback keeps the
# long-run average unbiased
total_c, total_e = jnp.zeros((32, 32)), jnp.zeros((32, 32))
with mesh:
    for i in range(20):
        mean, err = f(g, err)
        total_c += mean["w"]
        total_e += g["w"]
bias = float(jnp.max(jnp.abs(total_c - total_e)) / jnp.max(jnp.abs(total_e)))
print("BIAS", bias)
assert bias < 0.01
"""
    )
    assert "BIAS" in out


@pytest.mark.slow
def test_dryrun_tiny_mesh_subprocess(tmp_path):
    """End-to-end dry-run (lower+compile+roofline) for one arch on a tiny
    mesh carved from 512 forced host devices."""
    out = subprocess.run(
        [
            sys.executable, "-m", "repro.launch.dryrun",
            "--arch", "granite-8b", "--shape", "decode_32k",
            "--mesh", "tiny", "--reduced", "--out", str(tmp_path), "--force",
        ],
        capture_output=True, text=True, env=ENV, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    path = tmp_path / "granite-8b__decode_32k__tiny.json"
    data = json.loads(path.read_text())
    assert data["status"] == "ok"
    assert data["terms"]["dominant"] in ("compute", "memory", "collective")
    assert data["flops"] > 0


def test_all_cells_accounted():
    cells = configs.dryrun_cells()
    assert len(cells) == 40
    runnable = [c for c in cells if c[2]]
    skipped = [c for c in cells if not c[2]]
    assert len(runnable) == 32
    assert len(skipped) == 8
    for _, _, _, reason in skipped:
        assert reason
