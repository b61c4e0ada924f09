"""Property-based tests (hypothesis) for the sharding rules — the
invariants a 1000+-node deployment depends on:

  * every emitted PartitionSpec is valid for its shape (each sharded dim
    divisible by its mesh-axes product),
  * no mesh axis is used twice in one spec,
  * divisibility fallback never crashes, it replicates,
  * batch specs respect explicit shapes (global_batch=1 decode cells).
"""

import jax
import numpy as np
import pytest
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # minimal images: deterministic fallback shim
    from _hypothesis_shim import given, settings, st
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.configs.base import ParallelismConfig
from repro.distributed.sharding import ShardingRules

LOGICALS = [
    None, "batch", "embed", "heads", "kv_heads", "mlp", "vocab",
    "experts", "layers", "cache_len", "q_lora", "inner", "ssm_heads",
]


@pytest.fixture(scope="module")
def mesh():
    # 8 forced host devices are NOT available under the normal test
    # process (1 device) — use a 1x1 mesh for structural properties and
    # rely on tests/test_distributed.py subprocesses for multi-device.
    return make_mesh((1, 1), ("data", "model"))


@settings(max_examples=100, deadline=None)
@given(
    st.lists(st.sampled_from(LOGICALS), min_size=1, max_size=4),
    st.lists(st.integers(1, 512), min_size=1, max_size=4),
)
def test_spec_is_always_valid(axes, dims):
    mesh = make_mesh((1, 1), ("data", "model"))
    n = min(len(axes), len(dims))
    axes, dims = tuple(axes[:n]), tuple(dims[:n])
    rules = ShardingRules(mesh=mesh, plan=ParallelismConfig())
    spec = rules.spec_for(axes, dims)
    assert isinstance(spec, P)
    assert len(spec) == n
    used = []
    for part, size in zip(spec, dims):
        if part is None:
            continue
        names = part if isinstance(part, tuple) else (part,)
        total = int(np.prod([mesh.shape[a] for a in names]))
        assert size % total == 0
        used.extend(names)
    assert len(used) == len(set(used))  # no axis reused


def test_fallback_records_unshardable_axes():
    """40 experts on a 16-way model axis must replicate AND be recorded
    (the granite-moe §Perf finding)."""
    import os
    import subprocess
    import sys

    code = """
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=16'
import jax
from repro.configs.base import ParallelismConfig
from repro.distributed.sharding import ShardingRules
from repro.launch.mesh import make_mesh
mesh = make_mesh((1, 16), ("data", "model"))
rules = ShardingRules(mesh=mesh, plan=ParallelismConfig())
spec = rules.spec_for(("experts", "embed", "mlp"), (40, 64, 512))
assert spec[0] is None, spec           # 40 % 16 != 0 -> replicated
assert ("experts", 40) in rules.fallbacks
spec2 = rules.spec_for(("experts",), (48,))
assert spec2[0] == "model"             # 48 % 16 == 0 -> sharded
print("OK")
"""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, PYTHONPATH=os.path.join(repo, "src")),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


@settings(max_examples=50, deadline=None)
@given(st.integers(1, 4), st.integers(1, 1024))
def test_batch_spec_shape_fallback(ndim, batch):
    mesh = make_mesh((1, 1), ("data", "model"))
    rules = ShardingRules(mesh=mesh, plan=ParallelismConfig())
    shape = (batch,) + (8,) * (ndim - 1)
    spec = rules.batch_spec(ndim, shape=shape)
    assert len(spec) == ndim
    for part, size in zip(spec, shape):
        if part is None:
            continue
        names = part if isinstance(part, tuple) else (part,)
        total = int(np.prod([mesh.shape[a] for a in names]))
        assert size % total == 0
