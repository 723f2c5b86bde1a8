"""Hypothesis property tests on system invariants."""
import json
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # container lacks hypothesis: deterministic stub
    from _hypothesis_stub import given, settings, strategies as st

from repro.distributed import compression as COMP
from repro.kernels.ref import ssd_scan_ref
from repro.models.ssm import ssd_chunked

REPO = pathlib.Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------------
# SSD: chunked == sequential for arbitrary small shapes
# ---------------------------------------------------------------------------


@given(st.integers(1, 3), st.sampled_from([2, 4, 8]), st.integers(1, 3),
       st.sampled_from([4, 8]), st.sampled_from([4, 8]),
       st.sampled_from([2, 4]), st.integers(0, 2 ** 31 - 1))
@settings(max_examples=25, deadline=None)
def test_ssd_chunked_equals_sequential(B, S, H, P, N, chunk, seed):
    if S % chunk:
        return
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    X = jax.random.normal(ks[0], (B, S, H, P))
    Adt = -jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))) * 0.5
    Bc = jax.random.normal(ks[2], (B, S, N))
    Cc = jax.random.normal(ks[3], (B, S, N))
    y1, s1 = ssd_chunked(X, Adt, Bc, Cc, chunk)
    y2, s2 = ssd_scan_ref(X, Adt, Bc, Cc)
    np.testing.assert_allclose(y1, y2, atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(s1, s2, atol=1e-4, rtol=1e-3)


# ---------------------------------------------------------------------------
# int8 EF compression: error bound holds for any tensor
# ---------------------------------------------------------------------------


@given(st.integers(0, 2 ** 31 - 1), st.floats(1e-4, 1e4))
@settings(max_examples=40, deadline=None)
def test_quantize_error_bounded_by_half_scale(seed, magnitude):
    x = jax.random.normal(jax.random.PRNGKey(seed), (64,)) * magnitude
    q, s = COMP.quantize_int8(x)
    assert float(jnp.abs(COMP.dequantize(q, s) - x).max()) <= \
        float(s) * 0.5 + 1e-6 * magnitude


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=20, deadline=None)
def test_ef_residual_stays_bounded(seed):
    """Error feedback must not accumulate: the residual stays within one
    quantization step of zero under a constant gradient."""
    g = jax.random.normal(jax.random.PRNGKey(seed), (32,))
    r = jnp.zeros_like(g)
    for _ in range(30):
        q, s, r = COMP.ef_quantize(g, r)
    assert float(jnp.abs(r).max()) <= float(s) + 1e-6


# ---------------------------------------------------------------------------
# compressed_psum on a REAL 4-device pod axis (subprocess)
# ---------------------------------------------------------------------------

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, "src")
import json
import jax
import jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P
from repro.distributed import compression as COMP

mesh = jax.make_mesh((4,), ("pod",), axis_types=(AxisType.Auto,))
# per-pod distinct gradients: mean must come out right through int8
g = jnp.stack([jnp.linspace(-1, 1, 64) * (i + 1) for i in range(4)])
r = jnp.zeros((4, 64))

def f(g, r):
    out, new_r = COMP.compressed_psum({"w": g[0]}, {"w": r[0]}, "pod")
    return out["w"][None], new_r["w"][None]

out, _ = jax.shard_map(f, mesh=mesh, in_specs=(P("pod"), P("pod")),
                       out_specs=(P("pod"), P("pod")))(g, r)
true_mean = g.mean(0)
err = float(jnp.abs(out[0] - true_mean).max())
print(json.dumps({"err": err, "devices": jax.device_count()}))
"""


def test_compressed_psum_four_devices():
    out = subprocess.run([sys.executable, "-c", _SCRIPT],
                         capture_output=True, text=True, cwd=REPO,
                         timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["devices"] == 4
    assert rec["err"] < 0.05   # int8 mean of 4 pods within quant error
