"""Multi-stream switcher scaling (paper App. D): the batched fused-scan
engine vs the seed's per-stream Python loop.

The seed drove V streams through V separate ``lax.scan`` dispatches per
planning window (plus a fresh trace whenever the tail window shrank).
The batched engine stacks the tables pytree, vmaps the decision over the
stream axis, and runs ONE scan — so per-window dispatch cost is constant
in V and padded tails never recompile. Reports per-V wall-clock,
throughput (segment-decisions/s), speedup over the loop, and the jit
cache deltas proving zero recompiles after warmup.

    PYTHONPATH=src:. python benchmarks/multi_stream_bench.py [--tiny]

``--tiny`` runs a seconds-scale smoke configuration (used by
``scripts/tier1.sh --bench-smoke`` so this entry point cannot rot).
"""
from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.core.switcher import (compile_cache_size, init_state,
                                 init_state_multi, pad_window_multi,
                                 run_window, run_window_multi, stack_tables)
from benchmarks.overheads import _tables

WINDOWS = 12          # planning windows per run (last one is a short tail)
W = 512               # segments per window
TAIL = 197            # length of the final (padded) window


def _stream_data(V, K, C, W, windows, tail, seed=0):
    rng = np.random.default_rng(seed)
    tables = [_tables(K, C, seed=v) for v in range(V)]
    alphas = rng.random((V, C, K)).astype(np.float32)
    alphas /= alphas.sum(-1, keepdims=True)
    T = (windows - 1) * W + tail
    quals = jnp.asarray(rng.random((V, T, K)), jnp.float32)
    arrs = jnp.asarray(0.5 + rng.random((V, T)), jnp.float32)
    return tables, jnp.asarray(alphas), quals, arrs, T


def _run_loop(tables, alphas, quals, arrs, T, W):
    """The seed implementation: V per-stream scans per window, tail
    window traced at its own (shorter) length — V dispatches/window plus
    one recompile for the tail shape, per stream."""
    states = [init_state(tb) for tb in tables]
    total = 0.0
    t = 0
    while t < T:
        W_t = min(W, T - t)
        for v in range(len(tables)):
            states[v], outs = run_window(states[v], quals[v, t:t + W_t],
                                         arrs[v, t:t + W_t], alphas[v],
                                         tables[v])
            total += float(np.asarray(outs["qual"]).sum())
        t += W_t
    return total


def _run_batched(tab_stack, states, alphas, quals, arrs, T, W):
    """The batched engine: one fused scan per window, tail padded to W."""
    total = 0.0
    t = 0
    while t < T:
        W_t = min(W, T - t)
        q_w, a_w, valid = pad_window_multi(quals[:, t:t + W_t],
                                           arrs[:, t:t + W_t], W)
        states, outs = run_window_multi(states, q_w, a_w, alphas, tab_stack,
                                        valid=valid)
        total += float(np.asarray(outs["qual"]).sum())
        t += W_t
    return total


def run(verbose: bool = True, tiny: bool = False):
    rows = []
    K, C = 8, 4
    W_, windows, tail = (64, 3, 23) if tiny else (W, WINDOWS, TAIL)
    for V in ((1, 4) if tiny else (1, 2, 4, 8)):
        tables, alphas, quals, arrs, T = _stream_data(V, K, C, W_, windows,
                                                      tail, seed=V)
        tab_stack = stack_tables(tables)

        # ---- seed loop ------------------------------------------------
        _run_loop(tables, alphas, quals, arrs, T, W_)      # warmup
        t0 = time.perf_counter()
        q_loop = _run_loop(tables, alphas, quals, arrs, T, W_)
        dt_loop = time.perf_counter() - t0

        # ---- batched engine -------------------------------------------
        _run_batched(tab_stack, init_state_multi(tables), alphas, quals,
                     arrs, T, W_)                          # warmup
        _, multi0 = compile_cache_size()
        t0 = time.perf_counter()
        q_bat = _run_batched(tab_stack, init_state_multi(tables), alphas,
                             quals, arrs, T, W_)
        dt_bat = time.perf_counter() - t0
        _, multi1 = compile_cache_size()
        recompiles = multi1 - multi0

        assert abs(q_loop - q_bat) < 1e-3 * max(abs(q_loop), 1.0), \
            f"batched engine diverged: {q_loop} vs {q_bat}"
        assert recompiles == 0, f"{recompiles} recompiles after warmup"
        decisions = V * T
        rows.append((V, dt_loop, dt_bat, dt_loop / dt_bat))
        if verbose:
            emit(f"multi_stream/V{V}",
                 dt_bat / decisions * 1e6,
                 f"loop={dt_loop * 1e3:.1f}ms;batched={dt_bat * 1e3:.1f}ms;"
                 f"speedup={dt_loop / dt_bat:.2f}x;"
                 f"throughput={decisions / dt_bat / 1e3:.0f}kdec/s;"
                 f"recompiles=0")
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    run(tiny="--tiny" in sys.argv[1:])
