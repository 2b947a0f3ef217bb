"""Paper App. B (Figs. 16/17): the idealized per-segment forecaster vs
Skyscraper's category-histogram design; KMeans vs GMM clustering."""
from __future__ import annotations

import numpy as np

from benchmarks.common import emit, fitted, stream
from repro.core import ingest as IG


def run(verbose: bool = True):
    # low provisioning: misallocating expensive configs actually hurts
    f = fitted("covid", 4, 3)
    s = stream("covid", days=1.0)
    # Skyscraper (practical forecasting task)
    sky = IG.run_skyscraper(f, s, n_cores=4, cloud_budget_core_s=5000.0,
                            plan_days=0.25, forecast_mode="model")
    # idealized design: per-segment quality forecast = time-of-day average
    # of the previous day (App. B.1) fed to the knapsack == run_optimum on
    # the SHIFTED stream (yesterday's qualities as the prediction)
    quals = s.quality(f.power, seed=0)
    day = int(86400 / s.segment_seconds)
    pred = np.roll(quals, day, axis=0)      # yesterday's quality as forecast
    import jax.numpy as jnp
    from repro.core.planner import solve_lp_lagrangian
    T = s.n_segments
    budget = 4 * s.segment_seconds * T + 5000.0 / IG.CLOUD_PREMIUM
    alpha = solve_lp_lagrangian(jnp.asarray(pred), jnp.asarray(f.cost),
                                jnp.full((T,), 1.0 / T), budget / T)
    k_sel = np.asarray(alpha).argmax(1)
    q_ideal = float(quals[np.arange(T), k_sel].sum())
    qmax = (1.0 - s.difficulty * (1.0 - 0.85 * f.power.max())).sum()
    ideal_pct = 100.0 * q_ideal / qmax
    opt = IG.run_optimum(f, s, n_cores=4, cloud_budget_core_s=5000.0)
    if verbose:
        emit("design_alt/idealized_per_segment", ideal_pct * 1e4,
             f"quality={ideal_pct:.1f}% (forecast noise hurts)")
        emit("design_alt/skyscraper", sky.quality_pct * 1e4,
             f"quality={sky.quality_pct:.1f}%")
        emit("design_alt/optimum_ground_truth", opt.quality_pct * 1e4,
             f"quality={opt.quality_pct:.1f}%")
    # KMeans vs GMM content categories (Fig. 17)
    from repro.core.categories import kmeans
    rng = np.random.default_rng(0)
    samp = rng.choice(len(quals), 800, replace=False)
    km_centers, _ = kmeans(quals[samp], 4)
    try:
        from scipy.stats import multivariate_normal  # noqa: F401
        # lightweight EM-GMM (diagonal) for the comparison
        X = quals[samp]
        mu = np.asarray(km_centers) + rng.normal(0, 0.02, km_centers.shape)
        var = np.ones_like(mu) * 0.05
        pi = np.ones(4) / 4
        for _ in range(30):
            logp = -0.5 * (((X[:, None] - mu[None]) ** 2) / var[None]
                           + np.log(var[None])).sum(-1) + np.log(pi)[None]
            logp -= logp.max(1, keepdims=True)
            resp = np.exp(logp)
            resp /= resp.sum(1, keepdims=True)
            nk = resp.sum(0) + 1e-9
            mu = (resp[..., None] * X[:, None]).sum(0) / nk[:, None]
            var = ((resp[..., None] * (X[:, None] - mu[None]) ** 2).sum(0)
                   / nk[:, None]) + 1e-4
            pi = nk / nk.sum()
        drift = float(np.abs(np.sort(mu, 0) - np.sort(np.asarray(km_centers),
                                                      0)).mean())
        if verbose:
            emit("design_alt/kmeans_vs_gmm_center_drift", drift * 1e6,
                 f"mean |centers| gap={drift:.4f} (same clusters)")
    except ImportError:
        pass
    return sky.quality_pct, ideal_pct


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
