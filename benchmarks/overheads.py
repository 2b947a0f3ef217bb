"""Paper Fig. 13 (§5.5): knob-switcher and knob-planner decision
overheads vs problem size — plus the beyond-paper Lagrangian-vs-scipy
planner comparison."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.core.planner import solve_lp_lagrangian, solve_lp_scipy
from repro.core.switcher import SwitchTables, init_state, switch_step


def _tables(K, C, P=8, seed=0):
    rng = np.random.default_rng(seed)
    power = np.sort(rng.random(K)).astype(np.float32)
    cost = np.sort(rng.random(K) * 20 + 0.5).astype(np.float32)
    return SwitchTables(
        centers=jnp.asarray(np.sort(rng.random((C, K)), 0), jnp.float32),
        power=jnp.asarray(power), cost=jnp.asarray(cost),
        place_rt=jnp.asarray(rng.random((K, P)) * 3, jnp.float32),
        place_on=jnp.asarray(rng.random((K, P)) * 10, jnp.float32),
        place_cl=jnp.asarray(rng.random((K, P)) * 5, jnp.float32),
        place_valid=jnp.ones((K, P), bool),
        rank_pos=jnp.asarray(np.argsort(np.argsort(-power)), jnp.int32),
        tau=2.0, buffer_cap_s=1e4, cloud_budget=1e6)


def run(verbose: bool = True):
    rows = []
    # switcher latency vs (K x P) sizes (paper: worst case linear in #plc)
    # two numbers: eager per-call (python dispatch included) and the
    # scan-amortized per-decision cost (what the ingestion loop pays)
    from repro.core.switcher import run_window
    for K, C in [(4, 3), (8, 4), (16, 8), (64, 8), (256, 16)]:
        t = _tables(K, C)
        st = init_state(t)
        alpha = jnp.ones((C, K)) / K
        q = jnp.full((K,), 0.5)
        st, _ = switch_step(st, q, jnp.float32(1.0), alpha, t)  # warmup
        n = 300
        t0 = time.perf_counter()
        for _ in range(n):
            st, out = switch_step(st, q, jnp.float32(1.0), alpha, t)
        _ = float(out["qual"])
        us = (time.perf_counter() - t0) / n * 1e6
        T = 4096
        quals = jnp.full((T, K), 0.5)
        arr = jnp.ones((T,))
        st2, o = run_window(init_state(t), quals, arr, alpha, t)  # warmup
        jax.block_until_ready(o["qual"])
        t0 = time.perf_counter()
        st2, o = run_window(init_state(t), quals, arr, alpha, t)
        jax.block_until_ready(o["qual"])
        us_scan = (time.perf_counter() - t0) / T * 1e6
        rows.append(("switcher", K, C, us_scan))
        if verbose:
            emit(f"overhead/switcher/K{K}_C{C}", us_scan,
                 f"scan-amortized/decision; eager={us:.0f}us; "
                 + ("paper_bound_ok" if us_scan < 500 else "OVER"))
    # planner latency vs (C x K)
    rng = np.random.default_rng(0)
    for K, C in [(8, 4), (32, 8), (128, 16), (512, 32)]:
        qual = jnp.asarray(rng.random((C, K)), jnp.float32)
        cost = jnp.asarray(rng.random(K) * 10 + 0.1, jnp.float32)
        r = jnp.asarray(np.ones(C) / C, jnp.float32)
        solve_lp_lagrangian(qual, cost, r, 3.0).block_until_ready()
        n = 50
        t0 = time.perf_counter()
        for _ in range(n):
            solve_lp_lagrangian(qual, cost, r, 3.0).block_until_ready()
        us_l = (time.perf_counter() - t0) / n * 1e6
        t0 = time.perf_counter()
        for _ in range(5):
            solve_lp_scipy(np.asarray(qual), np.asarray(cost),
                           np.asarray(r), 3.0)
        us_s = (time.perf_counter() - t0) / 5 * 1e6
        rows.append(("planner", K, C, us_l))
        if verbose:
            emit(f"overhead/planner_lagrangian/K{K}_C{C}", us_l,
                 f"scipy={us_s:.0f}us;speedup={us_s / us_l:.0f}x")
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
