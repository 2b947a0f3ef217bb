"""The plain reference a run is compared with, and the comparison.

Plain numpy, importing nothing of the program. What it reads from a
run: the rows that the timed ticks landed in the warehouse (read back
from the store), the standing answers and the alert's fired mask as the
program returned them, every plan the pool put in force, and the
offline fit's outputs (its kept configurations, their costs and quality
ranks, and the content classes' centres). It takes none of these on
trust: the fit is recomputed from the seed's unlabeled qualities and
the declared work (``reference_fit``; the centres are checked to be a
k-means fixed point of those qualities), and every plan is checked
against the LP optimum on the checked centres. The switch is then
stepped with the checked tables. Everything else it computes itself
from the seed.

Numbers compared, each against a limit in the driver's limits file:

- ``fit_bad``: kept configurations, costs or quality ranks that differ
  from the reference fit's, and content classes out of the order of
  their mean quality;
- ``centers_gap``: the mean gap between a profiled segment's class
  centre and the float64 mean of the quality vectors of that class;
- ``plan_bad``: plans put in force (one per stream and replan) that
  are an optimum of the planner's LP for no forecast (``plans_bad``);
- ``rows_missing``: tick rows not landed where the pool's slot order
  puts them (each tick lands one row per live stream, stream ids in
  slot order), plus rows landed beyond them;
- ``rows_bad_pct``: share of the sampled streams' tick rows that the
  switch and shed semantics, stepped once from that stream's earlier
  rows, do not give: category, configuration (the deficit argmax, or
  a near tie within ``TIE`` where a rounded division decides it),
  buffer, on-prem and cloud work,
  quality (the Transform's result for the chosen configuration, 0 when
  dropped or shed) and the output vector;
- ``standing_count_bad``: standing answers' groups whose row count or
  validity differs from a rescan;
- ``standing_gap``: the widest relative gap between a standing
  answer's value and the rescan's, in float64;
- ``alert_bad``: streams whose buffer high-water alert, its maximum or
  its count differs from the rescan's.
"""
from __future__ import annotations

import numpy as np

TIE = 1e-6                     # deficit scores this close are tied
BIG = 10 ** 6
PARETO_STEP = 1e-6             # the fit keeps a configuration that gains more
PLAN_TOL = 1e-3                # relative slack of a plan's price and spend
LLOYD_ITERS = 50


# -- the offline fit ------------------------------------------------------
def reference_fit(unl, work, num_cores: int, max_k: int, n_cat: int,
                  seed: int, dtype=np.float32):
    """The fit's tables from its inputs: ``unl`` (segments,
    configurations) float32 qualities and ``work`` the declared
    core-seconds per segment. Configurations are taken cheapest first
    while their mean quality rises by more than ``PARETO_STEP``, at
    most ``max_k``; ranks order them by mean quality, best first; the
    centres are k-means of their quality vectors (``kmeans``). Means
    and centres are taken in ``dtype``."""
    q = np.ascontiguousarray(np.asarray(unl, np.float32))
    mq = q.astype(dtype).mean(axis=0, dtype=dtype).astype(np.float64)
    runtimes = np.asarray(work, np.float64) / num_cores
    keep, best = [], -1.0
    for i in np.argsort(runtimes, kind="stable"):
        if mq[i] > best + PARETO_STEP:
            keep.append(int(i))
            best = mq[i]
    keep = keep[:max_k]
    quals = q[:, keep]
    return {"kept": keep, "cost": runtimes[keep] * num_cores,
            "rank_pos": np.argsort(np.argsort(-mq[keep], kind="stable"),
                                   kind="stable"),
            "quals": quals,
            "centers": kmeans(quals, n_cat, seed, dtype)}


def kmeans_pp_init(Q, k: int, seed: int):
    """k-means++ seeding from ``seed`` (the fit's own rule)."""
    rng = np.random.default_rng(seed)
    n = Q.shape[0]
    centers = [Q[rng.integers(n)]]
    for _ in range(k - 1):
        d2 = np.min([np.sum((Q - c) ** 2, axis=1) for c in centers], axis=0)
        s = d2.sum()
        if not np.isfinite(s) or s <= 1e-12:
            centers.append(Q[rng.integers(n)])
            continue
        centers.append(Q[rng.choice(n, p=d2 / s)])
    return np.stack(centers)


def lloyd_update(Q, centers, dtype=np.float64):
    """One Lloyd step in ``dtype``: each centre moves to the mean of the
    vectors nearest to it (a centre with none stays)."""
    Qd = np.asarray(Q).astype(dtype)
    C = np.asarray(centers).astype(dtype)
    d = ((Qd[:, None, :] - C[None]) ** 2).sum(axis=-1, dtype=dtype)
    near = d.argmin(axis=1)
    new = C.copy()
    for c in range(C.shape[0]):
        sel = near == c
        if sel.any():
            new[c] = (Qd[sel].sum(axis=0, dtype=dtype)
                      / dtype(sel.sum())).astype(dtype)
    return new


def kmeans(Q, k: int, seed: int, dtype=np.float64):
    """(k, configurations) centres: k-means++ seeding, ``LLOYD_ITERS``
    Lloyd steps in ``dtype``, ordered by mean quality, lowest first."""
    C = kmeans_pp_init(np.asarray(Q, np.float32), k, seed).astype(dtype)
    for _ in range(LLOYD_ITERS):
        C = lloyd_update(Q, C, dtype)
    return C[np.argsort(C.astype(np.float64).mean(axis=1), kind="stable")]


def centers_gap(Q, centers) -> float:
    """Mean, over the profiled segments and configurations, of the gap
    between the centre of a segment's class (its nearest) and the
    float64 mean of that class: 0 at a k-means fixed point of ``Q``.
    A mean over segments, not the widest gap: a class of one segment
    carries the rounding of a single value, and would set the widest."""
    Q = np.asarray(Q, np.float64)
    C = np.asarray(centers, np.float64)
    near = ((Q[:, None, :] - C[None]) ** 2).sum(axis=-1).argmin(axis=1)
    return float(np.abs(lloyd_update(Q, C) - C)[near].mean())


def fit_compare(fit, ref) -> int:
    """Tables of the fit that differ from the reference fit's."""
    kept, want = list(fit["kept"]), list(ref["kept"])
    if kept != want:
        return max(len(kept), len(want))
    cost = np.asarray(fit["cost"], np.float64)
    bad = int((np.abs(cost - ref["cost"])
               > 1e-9 * np.abs(ref["cost"])).sum())
    bad += int((np.asarray(fit["rank_pos"]) != ref["rank_pos"]).sum())
    m = np.asarray(fit["centers"], np.float64).mean(axis=1)
    return bad + int((np.diff(m) < 0).sum())


# -- the planner ----------------------------------------------------------
def reference_plan(centers, dtype=np.float64):
    """(C, K) optimum of the planner's LP where the budget does not
    bind: each class on its best configuration."""
    q = np.asarray(centers).astype(dtype)
    plan = np.zeros(q.shape, np.float32)
    plan[np.arange(q.shape[0]), q.argmax(axis=1)] = 1.0
    return plan


def plans_bad(plans, centers, cost, budget: float) -> int:
    """Plans of a stream (one per stream and replan) in ``plans``, a list
    of ``(first_tick, (S, C, K) plan)``, that are an optimum of the
    planner's LP for no forecast. The LP is a product of simplices
    under one budget, so a plan is an optimum for some forecast r if
    and only if its rows are mixing histograms, one price of compute
    lam >= 0 makes every row's configurations the best of its class by
    quality - lam * cost, and some r spends the budget (lam > 0) or
    stays within it (lam = 0)."""
    q = np.asarray(centers, np.float64)                      # (C, K)
    cost = np.asarray(cost, np.float64)
    D = cost[None, :] - cost[:, None]          # [k, j]: cost_j - cost_k
    G = q[:, None, :] - q[:, :, None]          # [c, k, j]: q_j - q_k
    with np.errstate(divide="ignore", invalid="ignore"):
        B = G / D[None]
    bad = 0
    for _, a in plans:
        a = np.asarray(a, np.float64)                        # (S, C, K)
        sup = (a > 0)[..., None]                             # (S, C, K, 1)
        lo = np.where(sup & (D > 0), B, -np.inf).max(axis=(1, 2, 3))
        hi = np.where(sup & (D < 0), B, np.inf).min(axis=(1, 2, 3))
        tie = (sup & (D == 0) & (G > PLAN_TOL)).any(axis=(1, 2, 3))
        lo = np.maximum(lo, 0.0)
        spend = (a * cost).sum(axis=-1)                      # (S, C)
        ok = ((a >= 0).all(axis=(1, 2))
              & (np.abs(a.sum(axis=-1) - 1.0) <= 1e-5).all(axis=1)
              & ~tie & (lo <= hi * (1 + PLAN_TOL) + PLAN_TOL)
              & (spend.min(axis=1) <= budget * (1 + PLAN_TOL))
              & ((lo <= PLAN_TOL)
                 | (spend.max(axis=1) >= budget * (1 - PLAN_TOL))))
        bad += int((~ok).sum())
    return bad


def rows_missing(tail, n_ticks: int, V: int) -> int:
    """Rows not where the fixed pool's slot order puts them."""
    t, sid = np.asarray(tail["t"]), np.asarray(tail["stream_id"])
    want_t = np.repeat(np.arange(n_ticks), V)
    want_s = np.tile(np.arange(V), n_ticks)
    n = min(len(t), len(want_t))
    off = int(((t[:n] != want_t[:n]) | (sid[:n] != want_s[:n])).sum())
    return off + abs(len(t) - len(want_t))


def sample_streams(seed: int, V: int, n: int, must=()) -> np.ndarray:
    rng = np.random.default_rng(int(seed) + 7)
    pick = rng.choice(V, size=min(n, V), replace=False)
    return np.unique(np.concatenate([pick, np.asarray(must, np.int64)])
                     .astype(np.int64))


def rows_bad(tail, n_ticks: int, V: int, sample, Q, alphas, prof,
             dtype=np.float32):
    """(bad, compared): one switch step per sampled stream and tick,
    from the state its earlier landed rows imply. ``alphas``: list of
    ``(first_tick, (S, C, K) plan)``; ``prof``: centers, rank_pos,
    cost, num_cores, tau, buffer_cap_s, cloud_budget, shed_watermark,
    arrival."""
    dt = dtype
    S = len(sample)
    centers = np.asarray(prof["centers"], np.float32).astype(dt)
    C, K = centers.shape
    rank = np.asarray(prof["rank_pos"], np.int64)
    cost32 = np.asarray(prof["cost"], np.float64).astype(np.float32)
    rt_all = np.float32(1.0) * (np.asarray(prof["cost"], np.float64)
                                / prof["num_cores"]).astype(np.float32)
    arr = np.float32(prof["arrival"])
    rt_eff = (rt_all * arr).astype(dt)
    on_eff = (cost32 * arr).astype(dt)
    tau = np.asarray(prof["tau"], np.float32).astype(dt)
    cap = np.asarray(prof["buffer_cap_s"], np.float32).astype(dt)
    wm = prof["shed_watermark"]
    hwm = None if wm is None else (np.float32(wm) * np.float32(
        prof["buffer_cap_s"])).astype(dt)
    used = np.zeros((S, C, K), np.float32)
    count = np.zeros((S, C), np.float32)
    buf = np.zeros(S, dt)
    k_cur = np.full(S, int(np.argmin(rank)), np.int64)
    qprev = np.ones(S, dt)
    cloud = np.zeros(S, np.float32)
    rows = np.arange(S)
    pos = np.asarray(sample, np.int64)
    plan_i, plan = 0, None
    bad = 0
    for i in range(n_ticks):
        while plan_i < len(alphas) and alphas[plan_i][0] <= i:
            plan = np.asarray(alphas[plan_i][1], np.float32).astype(dt)
            plan_i += 1
        at = i * V + pos
        c_l = tail["category"][at]
        k_l = tail["k"][at].astype(np.int64)
        q_l = tail["quality"][at]
        on_l = tail["on_core_s"][at]
        cl_l = tail["cloud_core_s"][at]
        b_l = tail["buffer_s"][at]
        out_l = tail["out"][at]
        # 1. classify from the previous segment's reported quality
        col = centers.T[k_cur]                                   # (S, C)
        c = np.argmin(np.abs(col - qprev[:, None]), axis=1)
        # 2. usage-deficit pick
        u, n = used[rows, c], np.maximum(count[rows, c], 1.0)[:, None]
        frac = u.astype(dt) / n.astype(dt)
        score = (plan[rows, c] - frac).astype(np.float64)
        first = np.argmax(score, axis=1)
        # a tie breaks to the first index, as argmax does; a near tie
        # may break either way where a division in it was rounded
        rounded = (frac.astype(np.float64) != u.astype(np.float64) / n)
        near = ((score >= score.max(axis=1, keepdims=True) - TIE)
                & (rounded | rounded[rows, first][:, None]))
        nxt = near.copy()
        nxt[rows, first] = True
        # 3. placement: cheapest feasible at or below the pick's rank
        headroom = (tau + (cap - buf)).astype(dt)
        feas = ((rt_eff[None, :] <= headroom[:, None])
                & (cloud[:, None] <= np.float32(prof["cloud_budget"])))
        any_feas = feas.any(axis=1)
        ok = np.zeros((S, K), bool)
        pos2 = np.where(feas, rank[None, :], BIG)
        for kn in range(K):
            cand = feas & (rank[None, :] >= rank[kn])
            pos1 = np.where(cand, rank[None, :], BIG)
            ksel = np.where(cand.any(axis=1), np.argmin(pos1, axis=1),
                            np.argmin(pos2, axis=1))
            ok[rows[nxt[:, kn]], ksel[nxt[:, kn]]] = True
        k_ok = ok[rows, np.clip(k_l, 0, K - 1)] & (k_l >= 0) & (k_l < K)
        kk = np.clip(k_l, 0, K - 1)
        rt = np.where(any_feas, rt_eff[kk], dt(0.0)).astype(dt)
        on = np.where(any_feas, on_eff[kk], dt(0.0)).astype(dt)
        b_sw = np.maximum((buf + rt).astype(dt) - tau, dt(0.0)).astype(dt)
        dropped = ~any_feas
        shed = (np.zeros(S, bool) if hwm is None
                else ~dropped & (buf >= hwm))
        b_ref = np.where(shed, np.maximum((buf - tau).astype(dt), dt(0.0)),
                         b_sw).astype(dt)
        on_ref = np.where(shed, dt(0.0), on).astype(dt)
        q_ref = np.where(dropped | shed, np.float32(0.0),
                         Q[i % len(Q)][pos, kk]).astype(dt)
        out_ref = (np.arange(K)[None, :] == kk[:, None]) * q_ref[:, None]
        wrong = ((c != c_l) | ~k_ok
                 | (b_ref.astype(np.float32) != b_l)
                 | (on_ref.astype(np.float32) != on_l)
                 | (cl_l != 0)
                 | (q_ref.astype(np.float32) != q_l)
                 | (out_ref.astype(np.float32) != out_l).any(axis=1))
        bad += int(wrong.sum())
        # the state the landed row implies
        cc = np.clip(c_l, 0, C - 1)
        used[rows, cc, kk] += 1.0
        count[rows, cc] += 1.0
        k_cur = kk
        buf = b_l.astype(dt)
        qprev = q_l.astype(dt)
        cloud = cloud + cl_l
    return bad, n_ticks * S


def rescan(spec, rows, dtype=np.float64):
    """(values, counts) of one standing query over its stream's rows."""
    if spec["kind"] == "window_mean":
        ids = np.clip(rows["t"] // spec["window"], 0, spec["num"] - 1)
        val = rows["quality"]
    else:
        ids = np.clip(rows["k"], 0, spec["num"] - 1)
        val = rows["category"]
    cnt = np.bincount(ids, minlength=spec["num"]).astype(np.int64)
    tot = np.zeros(spec["num"], dtype)
    for g in range(spec["num"]):        # in row order, in ``dtype``
        sel = np.asarray(val[ids == g]).astype(dtype)
        acc = dtype(0.0)
        for chunk in np.array_split(sel, max(1, len(sel) // 4096)):
            acc = dtype(acc + chunk.sum(dtype=dtype))
        tot[g] = acc
    if spec["kind"] == "window_mean":
        tot = (tot / np.maximum(cnt, 1).astype(dtype)).astype(dtype)
    return tot, cnt


def standing_compare(specs, answers, rows_of, dtype=np.float64):
    """(count_bad, widest relative value gap) of the standing answers
    ``answers[j] = (table, mask)`` against rescans in ``dtype``."""
    count_bad, gap = 0, 0.0
    for spec, (table, mask) in zip(specs, answers):
        ref, cnt = rescan(spec, rows_of(spec["sid"]), dtype)
        vcol = "quality" if spec["kind"] == "window_mean" else "category"
        got = np.asarray(table[vcol], np.float64)
        gcnt = np.asarray(table["count"], np.float64)
        count_bad += int(((gcnt != cnt) | (np.asarray(mask) != (cnt > 0)))
                         .sum())
        sel = cnt > 0
        if sel.any():
            r = np.asarray(ref, np.float64)[sel]
            rel = np.abs(got[sel] - r) / np.maximum(np.abs(r), 1e-30)
            gap = max(gap, float(rel.max()))
    return count_bad, gap


def alert_compare(alert, threshold: float, ref_max, ref_cnt) -> int:
    """Streams whose alert row differs from the rescan's maxima."""
    fired = np.asarray(alert["fired"], bool)
    table = alert["table"]
    has = ref_cnt > 0
    want = has & (np.float32(ref_max) > np.float32(threshold))
    gmax = np.asarray(table["buffer_s"], np.float32)
    gcnt = np.asarray(table["count"], np.float64)
    diff = ((fired != want) | (gcnt != ref_cnt)
            | (has & (gmax != np.float32(ref_max))))
    return int(diff.sum())


def judge(numbers, limits) -> bool:
    return all(numbers[k] is not None and numbers[k] <= limits[k]
               for k in limits)


def report_lines(numbers, limits):
    return [f"check {k}: {numbers[k]!r} limit {limits[k]!r}"
            for k in limits]
