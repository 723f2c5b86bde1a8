"""GPipe pipeline utility: numerical equivalence to the sequential scan,
verified on a real 4-device mesh in a subprocess (this process keeps 1 CPU
device)."""
import json
import pathlib
import subprocess
import sys

from repro.distributed.pipeline import bubble_fraction

REPO = pathlib.Path(__file__).resolve().parent.parent

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, "src")
import json
import jax
import jax.numpy as jnp
import numpy as np
from repro.distributed.pipeline import pipeline_apply

L, B, D, M = 8, 12, 16, 6
key = jax.random.PRNGKey(0)
kw, kb, kx = jax.random.split(key, 3)
params = {"w": jax.random.normal(kw, (L, D, D)) * 0.3,
          "b": jax.random.normal(kb, (L, D)) * 0.1}
x = jax.random.normal(kx, (B, D))

def block(p, h):
    return jnp.tanh(h @ p["w"] + p["b"])

# sequential reference
def seq(x):
    def one(h, p):
        return block(p, h), None
    out, _ = jax.lax.scan(one, x, params)
    return out
ref = seq(x)

mesh = jax.make_mesh((4,), ("pod",),
                     axis_types=(jax.sharding.AxisType.Auto,))
out = jax.jit(lambda p, x: pipeline_apply(
    block, p, x, mesh=mesh, axis="pod", microbatches=M))(params, x)
err = float(jnp.abs(out - ref).max())
print(json.dumps({"max_err": err, "devices": jax.device_count()}))
"""


def test_pipeline_matches_sequential_scan():
    out = subprocess.run([sys.executable, "-c", _SCRIPT],
                         capture_output=True, text=True, cwd=REPO,
                         timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["devices"] == 4
    assert rec["max_err"] < 1e-5, rec


def test_bubble_fraction():
    assert bubble_fraction(1, 8) == 0.0
    assert bubble_fraction(4, 12) == 3 / 15
    assert 0 < bubble_fraction(2, 2) < 1
