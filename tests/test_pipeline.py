"""Pipeline parallelism: executed equivalence on a real 2-device pod mesh.

Subprocess (needs XLA_FLAGS device-count before jax init): a 2-stage
pipeline over the pod axis must reproduce the plain forward pass exactly.
"""
from __future__ import annotations

import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import smoke_config
from repro.models import LM
from repro.sharding.rules import default_rules
from repro.train.pipeline import make_pipelined_forward

from repro.launch.mesh import make_test_mesh
mesh = make_test_mesh((2, 1, 1), ("pod", "data", "model"))
rules = default_rules(mesh).with_overrides(stack=("pod",))
cfg = dataclasses.replace(smoke_config("phi4-mini-3.8b"), dtype="float32",
                          num_layers=4)
model = LM(cfg, attn_chunk=8, remat="none", rules=rules)
params = model.init(jax.random.PRNGKey(0))
B, S = 8, 16
embeds = jnp.asarray(np.random.default_rng(0).normal(size=(B, S, cfg.d_model)),
                     jnp.float32)

# reference: plain forward up to final norm — recreate by running blocks only
from repro.models.lm import block_apply
positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
h = embeds
for p_idx in range(model.n_periods):
    blk = jax.tree.map(lambda x: x[p_idx], params["blocks"])
    for i, kind in enumerate(model.period_kinds):
        h, _ = block_apply(cfg, kind, blk[str(i)], h, positions, chunk=8)
ref = h

fwd = make_pipelined_forward(model, rules, num_microbatches=4)
pspecs = model.param_specs(rules)
ns = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                            is_leaf=lambda x: isinstance(x, P))
with mesh:
    params_sharded = jax.device_put(params, ns(pspecs))
    out = jax.jit(fwd)(params_sharded, embeds)
err = float(jnp.abs(out - ref).max())
scale = float(jnp.abs(ref).max())
assert err < 1e-3 * max(scale, 1.0), (err, scale)
print("PIPELINE_OK", err)
"""


def test_pipeline_matches_forward():
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, timeout=540,
        env={"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin:/usr/local/bin"},
        cwd=str(REPO))
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "PIPELINE_OK" in out.stdout
