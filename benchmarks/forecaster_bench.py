"""Paper Table 5/6 + Fig. 14/18: forecaster MAE vs horizon, vs input
featurization, and vs training-set size; end-to-end effect of the
horizon on Skyscraper quality."""
from __future__ import annotations

import jax
import numpy as np

from benchmarks.common import emit, fitted, stream
from repro.configs.workloads import COVID, MOT
from repro.core import ingest as IG
from repro.core.forecaster import (forecast, init_forecaster, make_dataset,
                                   train_forecaster)
from repro.core.offline import fit
from repro.data.stream import generate


def _labels(w, days, n_cat, seed=0):
    f = fit(w, n_cores=8, days_unlabeled=days, n_categories=n_cat, seed=seed)
    s = generate(w, days=days, seed=seed + 1)
    q = s.quality(f.power, seed=seed + 2)
    d = ((q[:, None, :] - f.centers[None]) ** 2).sum(-1)
    return d.argmin(1), f


def run(verbose: bool = True):
    rows = []
    for w, wname in ((COVID, "covid"), (MOT, "mot")):
        labels, f = _labels(w, days=18.0, n_cat=3)
        tau = w.segment_seconds
        # Table 5: MAE vs forecast horizon
        for days_ahead in (1, 2, 4, 8):
            horizon = min(int(days_ahead * 86400 / tau), len(labels) // 3)
            interval = max(1, int(2 * 86400 / 8 / tau))
            interval = min(interval, (len(labels) - horizon) // 16)
            X, Y = make_dataset(labels, 3, interval=interval, n_split=8,
                                horizon=horizon)
            p = init_forecaster(jax.random.PRNGKey(0), 8, 3)
            p, m = train_forecaster(p, X, Y, epochs=40)
            rows.append((wname, "horizon", days_ahead, m["val_mae"]))
            if verbose:
                emit(f"forecaster/{wname}/mae_h{days_ahead}d",
                     m["val_mae"] * 1e6, f"val_mae={m['val_mae']:.4f}")
        # Fig. 18: MAE vs number of training samples
        horizon = min(int(2 * 86400 / tau), len(labels) // 3)
        interval = min(max(1, int(2 * 86400 / 8 / tau)),
                       (len(labels) - horizon) // 16)
        X, Y = make_dataset(labels, 3, interval=interval, n_split=8,
                            horizon=horizon)
        for n in (50, 200, 700, len(X)):
            n = min(n, len(X))
            p = init_forecaster(jax.random.PRNGKey(0), 8, 3)
            p, m = train_forecaster(p, X[:n], Y[:n], epochs=40)
            if verbose:
                emit(f"forecaster/{wname}/mae_n{n}", m["val_mae"] * 1e6,
                     f"val_mae={m['val_mae']:.4f}")
    # Fig. 14: end-to-end quality, model vs oracle vs uniform forecast
    f = fitted("covid", 8, 3)
    s = stream("covid", days=1.0)
    for mode in ("model", "oracle", "uniform"):
        res = IG.run_skyscraper(f, s, n_cores=8,
                                cloud_budget_core_s=5000.0,
                                plan_days=0.25, forecast_mode=mode)
        if verbose:
            emit(f"forecaster/e2e_covid/{mode}", res.quality_pct * 1e4,
                 f"quality={res.quality_pct:.2f}%")
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
