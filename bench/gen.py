"""Seeded content for the benchmark's deployments, made on the device.

The content model is the paper's synthetic stream (section 5.2 of the
V-ETL paper, re-synthesised from its published statistics), written
here again so that the yardstick does not move with the program:

- latent content state: semi-Markov. A new run starts at a segment
  with probability 1 / (1 + dwell), dwell = max(2, dwell_seconds / tau)
  segments; a run's state is drawn with weights
  exp(-0.5 ((i - hardness * (n - 1)) / 0.9)^2) at the run's start, and
  the first run is state 0;
- hardness: a diurnal day bump with rush-hour shoulders for traffic
  cameras, else 0.5 + 0.25 sin(2 pi t / 8 h);
- difficulty: linspace(0.08, 0.92, n)[state] + N(0, 0.03), clipped to
  [0, 1];
- quality of a configuration of power p:
  clip(1 - difficulty * (1 - 0.85 p) + N(0, 0.02), 0, 1).

Every random draw is keyed by (seed, stream id, segment), so a block of
streams regenerates bit for bit what the whole fleet drew: the oracle
rebuilds any stream's history without keeping it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

DAY_S = 86_400.0
QUALITY_DISCOUNT = 0.85


def key_for(seed: int, purpose: int):
    """A PRNG key for ``purpose`` from a seed of up to 64 bits."""
    seed = int(seed)
    base = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(base, purpose)


def _hardness(t_sec, diurnal: bool):
    if diurnal:
        tod = (t_sec % DAY_S) / DAY_S
        day = jnp.exp(-0.5 * ((tod - 0.55) / 0.22) ** 2)
        rush = (jnp.exp(-0.5 * ((tod - 0.35) / 0.04) ** 2)
                + jnp.exp(-0.5 * ((tod - 0.73) / 0.04) ** 2))
        h = 0.15 + 0.6 * day + 0.5 * rush
    else:
        h = 0.5 + 0.25 * jnp.sin(2 * jnp.pi * t_sec / (DAY_S / 3))
    return jnp.clip(h, 0.05, 1.1)


def _difficulty_one(key, T: int, content):
    """(T,) difficulty of one stream."""
    n, tau = content["n_latent"], content["segment_seconds"]
    dwell = max(2, int(content["dwell_seconds"] / tau))
    k_run, k_state, k_noise = jax.random.split(key, 3)
    t = jnp.arange(T)
    start = (jax.random.uniform(k_run, (T,)) < 1.0 / (1 + dwell)).at[0] \
        .set(True)
    first = jax.lax.cummax(jnp.where(start, t, 0))
    target = _hardness(first * tau, content["diurnal"]) * (n - 1)
    w = jnp.exp(-0.5 * ((jnp.arange(n)[None, :] - target[:, None]) / 0.9)
                ** 2)
    cdf = jnp.cumsum(w, axis=1) / w.sum(axis=1, keepdims=True)
    u = jax.random.uniform(k_state, (T,))[first]
    state = jnp.minimum((u[:, None] > cdf).sum(axis=1), n - 1)
    state = jnp.where(first == 0, 0, state)
    base = jnp.linspace(0.08, 0.92, n)[state]
    d = jnp.clip(base + 0.03 * jax.random.normal(k_noise, (T,)), 0.0, 1.0)
    return d, state


def _qualities(key, d, power):
    """(K, T): Eq. 5 of the paper with measurement noise N(0, 0.02).
    Segments run along the minor axis, so no narrow (T, K) array
    pads its K configurations out to the TPU's 128 lanes."""
    noise = jax.random.normal(key, (power.shape[0], d.shape[0]))
    q = 1.0 - d[None, :] * (1.0 - QUALITY_DISCOUNT * power[:, None])
    return jnp.clip(q + 0.02 * noise, 0.0, 1.0)


@functools.partial(jax.jit, static_argnames=("T", "content"))
def live_qualities(key, stream_ids, power, *, T: int, content):
    """(V, K, T) float32: each live stream's quality of every fitted
    configuration on each of its next ``T`` segments (what the
    benchmark's Transform reports)."""
    content = dict(content)

    def one(sid):
        ks = jax.random.fold_in(key, sid)
        d, _ = _difficulty_one(ks, T, content)
        return _qualities(jax.random.fold_in(ks, 1), d, power)

    return jax.vmap(one)(stream_ids)


@functools.partial(jax.jit, static_argnames=("T", "content"))
def unlabeled_qualities(key, power, *, T: int, content):
    """(K_all, T) float32: one stream's quality of every configuration
    on ``T`` unlabeled segments (what the offline fit profiles)."""
    d, _ = _difficulty_one(key, T, dict(content))
    return _qualities(jax.random.fold_in(key, 1), d, power)


@functools.partial(jax.jit, static_argnames=("T", "content", "n_cat"))
def history_block(key, stream_ids, power, cost, hist, *, T: int, content,
                  n_cat: int):
    """Stream-major warehouse rows of ``stream_ids`` (S streams x T
    segments), each stream as a served camera would have logged it:
    the cheapest configuration whose quality reaches
    ``hist["quality_target"]`` (else the most powerful), falling back
    to the cheapest when its work would push the buffer past
    ``hist["buffer_limit_s"]``; the buffer fills by the work's wall
    time on ``hist["num_cores"]`` cores less the segment length."""
    content = dict(content)
    tau = jnp.float32(content["segment_seconds"])
    K = power.shape[0]
    cost = cost.astype(jnp.float32)
    rt = cost / jnp.float32(hist["num_cores"])
    by_cost = jnp.argsort(cost)
    cheapest = by_cost[0]
    strongest = jnp.argmax(power)

    def one(sid):
        ks = jax.random.fold_in(key, sid)
        d, state = _difficulty_one(ks, T, content)
        q = _qualities(jax.random.fold_in(ks, 1), d, power)   # (K, T)
        ok = q[by_cost] >= hist["quality_target"]
        want = jnp.where(ok.any(axis=0), by_cost[jnp.argmax(ok, axis=0)],
                         strongest)

        def step(buf, x):
            k = jnp.where(buf + rt[x] - tau > hist["buffer_limit_s"],
                          cheapest, x)
            buf = jnp.maximum(buf + rt[k] - tau, 0.0)
            return buf, (k, buf)

        _, (k, buf) = jax.lax.scan(step, jnp.float32(0.0), want)
        qk = jnp.take_along_axis(q, k[None, :], axis=0)[0]
        return {"stream_id": jnp.full((T,), sid, jnp.int32),
                "t": jnp.arange(T, dtype=jnp.int32),
                "category": jnp.minimum(state, n_cat - 1).astype(jnp.int32),
                "k": k.astype(jnp.int32),
                "quality": qk,
                "on_core_s": cost[k],
                "cloud_core_s": jnp.zeros((T,), jnp.float32),
                "buffer_s": buf}

    rows = {c: v.reshape(-1) for c, v in jax.vmap(one)(stream_ids).items()}
    rows["out"] = ((rows["k"][:, None] == jnp.arange(K)[None, :])
                   * rows["quality"][:, None])
    return rows


def content_key(cfg) -> tuple:
    """The hashable content parameters of a configuration."""
    c = cfg["content"]
    return (("diurnal", bool(c["diurnal"])),
            ("dwell_seconds", float(c["dwell_seconds"])),
            ("n_latent", int(c["n_latent"])),
            ("segment_seconds", float(cfg["segment_seconds"])))


def history(seed, cfg, stream_ids, power, cost, *, T: int, n_cat: int):
    """Rows of ``stream_ids``' history (device arrays)."""
    hist = {"quality_target": jnp.float32(cfg["history"]["quality_target"]),
            "buffer_limit_s": jnp.float32(cfg["history"]["buffer_limit_s"]),
            "num_cores": jnp.float32(cfg["fit"]["num_cores"])}
    return history_block(key_for(seed, 3),
                         jnp.asarray(np.asarray(stream_ids), jnp.int32),
                         jnp.asarray(power, jnp.float32),
                         jnp.asarray(cost, jnp.float32), hist, T=T,
                         content=content_key(cfg), n_cat=n_cat)
