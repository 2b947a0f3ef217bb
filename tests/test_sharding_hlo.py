"""Sharding layer + HLO analyzer: divisibility fallbacks, and the
trip-count-aware parser agreeing with cost_analysis on unrolled lowers
(where cost_analysis is exact) — run on a forced 8-device subprocess."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.distribution.sharding import (ParamMeta, shard, spec_for,
                                         use_mesh)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_shard_noop_without_mesh():
    x = jnp.ones((4, 4))
    y = shard(x, "batch", "tensor")
    assert y is x


def test_spec_for_drops_nondivisible():
    prog = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from jax.sharding import PartitionSpec as P
from repro.distribution.sharding import spec_for
from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh(model_axis=4)
# divisible -> sharded
s1 = spec_for((16, 8), ("fsdp", "tensor"), mesh)
assert s1 == P("data", "model"), s1
# vocab 92553 not divisible by 4 -> dropped
s2 = spec_for((92553, 16), ("vocab", "fsdp"), mesh)
assert s2 == P(None, "data"), s2
# heads 25 not divisible -> dropped
s3 = spec_for((4, 25, 64), (None, "tensor", None), mesh)
assert s3 == P(None, None, None), s3
print("SPEC_OK")
'''
    p = subprocess.run([sys.executable, "-c", prog],
                       env=dict(os.environ, PYTHONPATH=SRC),
                       capture_output=True, text=True, timeout=300)
    assert "SPEC_OK" in p.stdout, p.stdout + p.stderr


def test_hlo_parser_matches_cost_analysis_unrolled():
    """On an UNROLLED program cost_analysis is exact; the parser's
    dot-flops (x trip counts) must agree within a few % AND the scan
    version must parse to the same total."""
    prog = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch import hlo_analysis as HA
from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh(model_axis=4)
L, d, ff = 6, 128, 256
params = {"w1": jax.ShapeDtypeStruct((L, d, ff), jnp.float32),
          "w2": jax.ShapeDtypeStruct((L, ff, d), jnp.float32)}
ps = {"w1": NamedSharding(mesh, P(None, "data", "model")),
      "w2": NamedSharding(mesh, P(None, "model", "data"))}
x = jax.ShapeDtypeStruct((8, 32, d), jnp.float32)
xs = NamedSharding(mesh, P("data", None, None))

def run(unroll):
    def step(p, x):
        def body(h, w):
            h = h @ w["w1"]
            h = jax.nn.relu(h) @ w["w2"]
            return h, ()
        h, _ = jax.lax.scan(body, x, p, unroll=L if unroll else 1)
        return h.mean()
    co = jax.jit(step, in_shardings=(ps, xs)).lower(params, x).compile()
    flops_ca = (co.cost_analysis() or {}).get("flops", 0.0)
    parsed = HA.analyze(co.as_text())
    return flops_ca, parsed["dot_flops"]

ca_u, p_u = run(True)
ca_s, p_s = run(False)
# unrolled: parser ~= cost_analysis (both exact)
assert abs(p_u - ca_u) / ca_u < 0.05, (p_u, ca_u)
# scan: cost_analysis undercounts by ~L; parser must match the unrolled
assert abs(p_s - p_u) / p_u < 0.05, (p_s, p_u)
assert ca_s < ca_u / 2
print("HLO_OK", ca_u, p_u, ca_s, p_s)
'''
    p = subprocess.run([sys.executable, "-c", prog],
                       env=dict(os.environ, PYTHONPATH=SRC),
                       capture_output=True, text=True, timeout=600)
    assert "HLO_OK" in p.stdout, p.stdout + p.stderr


def test_param_meta_tree_roundtrip():
    from repro.distribution.sharding import abstract_tree, init_tree
    meta = {"a": ParamMeta((4, 8), ("fsdp", "tensor")),
            "n": ParamMeta((8,), (None,), "ones")}
    tree = init_tree(meta, jax.random.PRNGKey(0))
    ab = abstract_tree(meta)
    assert tree["a"].shape == ab["a"].shape == (4, 8)
    np.testing.assert_allclose(np.asarray(tree["n"]), 1.0)
