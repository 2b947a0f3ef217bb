"""int8 gradient compression: quantization error bounds, unbiasedness of
stochastic rounding, and error-feedback convergence in a DP training
loop (run on a forced multi-device mesh in a subprocess where needed —
here single-process psum via shard_map on a 1-device mesh plus math
properties)."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.distribution.compression import (compressed_psum, dequantize,
                                            quantize_int8)


def test_quantization_error_bound():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (1000,)) * 3.0
    q, scale = quantize_int8(x, jax.random.PRNGKey(1))
    err = np.abs(np.asarray(dequantize(q, scale) - x))
    assert err.max() <= float(scale) + 1e-6


def test_stochastic_rounding_unbiased():
    key = jax.random.PRNGKey(0)
    x = jnp.full((20_000,), 0.3)
    q, scale = quantize_int8(x, key)
    mean = float(dequantize(q, scale).mean())
    np.testing.assert_allclose(mean, 0.3, rtol=2e-2)


def test_quantize_roundtrip_bound_per_chunk():
    """The warehouse cold tier quantizes PER CHUNK (vmapped
    quantize_int8 with one scale per chunk): every chunk's round-trip
    error is bounded by that chunk's own scale = max|x_chunk|/127, so a
    quiet chunk is not degraded by a loud one."""
    key = jax.random.PRNGKey(3)
    n_chunks, chunk = 8, 512
    # chunk c scaled by 10^c: dynamic ranges differ by 7 orders
    mags = 10.0 ** jnp.arange(n_chunks, dtype=jnp.float32)
    x = jax.random.normal(key, (n_chunks, chunk)) * mags[:, None]
    keys = jax.random.split(jax.random.PRNGKey(4), n_chunks)
    q, scales = jax.vmap(quantize_int8)(x, keys)
    assert q.dtype == jnp.int8 and scales.shape == (n_chunks,)
    deq = jax.vmap(dequantize)(q, scales)
    err = np.abs(np.asarray(deq - x))
    per_chunk_bound = np.asarray(scales) + 1e-6
    assert (err.max(axis=1) <= per_chunk_bound).all()
    # per-chunk scales: the quiet chunk's error stays ~1e7x below the
    # loud chunk's (a single shared scale would wipe the quiet chunk)
    assert err[0].max() <= float(scales[-1]) * 1e-5


def test_compressed_psum_error_feedback_unbiased_over_steps():
    """compressed_psum itself (through shard_map on a 1-device 'pod'
    mesh): carrying its error residual across steps makes the
    accumulated compressed reduction converge to the true accumulated
    mean — compression noise stays unbiased over steps."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import auto_mesh

    mesh = auto_mesh((1,), ("pod",))
    spec = P()
    # build + jit the shard_map ONCE (key is a traced operand) so the
    # 200-step loop reuses a single executable
    step = jax.jit(shard_map(
        lambda x, e, k: compressed_psum(x, "pod", k, e),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=(spec, spec)))

    key = jax.random.PRNGKey(5)
    true_sum = jnp.zeros((256,))
    comp_sum = jnp.zeros((256,))
    err = jnp.zeros((256,))
    for _ in range(200):
        key, k1, k2 = jax.random.split(key, 3)
        g = jax.random.normal(k1, (256,)) * 0.1
        red, err = step(g, err, k2)
        true_sum = true_sum + g          # psum mean over 1 pod == g
        comp_sum = comp_sum + red
    rel = float(jnp.linalg.norm(comp_sum - true_sum)
                / jnp.linalg.norm(true_sum))
    assert rel < 0.02, rel
    # the residual itself stays bounded by one quantization step
    assert float(jnp.abs(err).max()) < 0.1


def test_error_feedback_recovers_signal():
    """With error feedback, the accumulated compressed signal converges
    to the true accumulated signal (compression noise does not bias)."""
    key = jax.random.PRNGKey(2)
    true_sum = jnp.zeros((256,))
    comp_sum = jnp.zeros((256,))
    err = jnp.zeros((256,))
    for t in range(200):
        key, k1, k2 = jax.random.split(key, 3)
        g = jax.random.normal(k1, (256,)) * 0.1
        q, scale = quantize_int8(g + err, k2)
        deq = dequantize(q, scale)
        err = (g + err) - deq
        true_sum = true_sum + g
        comp_sum = comp_sum + deq
    rel = float(jnp.linalg.norm(comp_sum - true_sum)
                / jnp.linalg.norm(true_sum))
    assert rel < 0.02, rel
