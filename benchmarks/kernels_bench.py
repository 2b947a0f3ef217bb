"""Pallas kernel harness: FLOP counts + interpret-mode allclose status
(wall-time on CPU interpret mode is NOT a perf claim; TPU perf comes from
the roofline analysis in benchmarks/roofline.py)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.kernels import ops, ref


def run(verbose: bool = True):
    key = jax.random.PRNGKey(0)
    # flash attention
    B, S, H, G, D = 1, 256, 4, 2, 64
    q = jax.random.normal(key, (B, S, H, D))
    k = jax.random.normal(key, (B, S, G, D))
    v = jax.random.normal(key, (B, S, G, D))
    t0 = time.perf_counter()
    out = ops.flash_attention(q, k, v, block_q=64, block_k=64)
    out.block_until_ready()
    us = (time.perf_counter() - t0) * 1e6
    err = float(jnp.abs(out - ref.flash_attention_ref(q, k, v)).max())
    flops = 4 * B * S * S * H * D
    if verbose:
        emit("kernel/flash_attention_256", us,
             f"flops={flops:.2e};allclose_err={err:.1e}")
    # ssd
    B, S, H, P, N = 1, 256, 4, 32, 64
    x = jax.random.normal(key, (B, S, H, P)) * 0.3
    dt = jax.nn.softplus(jax.random.normal(key, (B, S, H)))
    A = -jnp.exp(jax.random.normal(key, (H,)) * 0.3)
    Bm = jax.random.normal(key, (B, S, 1, N)) * 0.3
    Cm = jax.random.normal(key, (B, S, 1, N)) * 0.3
    t0 = time.perf_counter()
    y = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=64)
    y.block_until_ready()
    us = (time.perf_counter() - t0) * 1e6
    err = float(jnp.abs(y - ref.ssd_ref(x, dt, A, Bm, Cm)).max())
    if verbose:
        emit("kernel/ssd_256", us, f"allclose_err={err:.1e}")
    # frame downsample
    f = jax.random.normal(key, (4, 720, 1280, 3))
    t0 = time.perf_counter()
    d = ops.downsample(f, factor=2, block=64)
    d.block_until_ready()
    us = (time.perf_counter() - t0) * 1e6
    err = float(jnp.abs(d - ref.downsample_ref(f, 2)).max())
    if verbose:
        emit("kernel/downsample_720p_x2", us,
             f"bytes={f.size * 4:.2e};allclose_err={err:.1e}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
