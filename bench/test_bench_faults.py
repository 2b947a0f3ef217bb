"""A run whose timed path is broken underneath comes out not correct:
a tick that returns its state unchanged, half of a tick's rows left
out, an answer altered where it is produced, a replan that puts a
plan in force that is no optimum of its LP."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench.cpu_scale import run_tiny

CELL = "covid-fleet.saturate"


def test_state_left_unchanged_is_caught(monkeypatch):
    from repro.core import api
    real = api._pool_tick

    def frozen(state, *args):
        _, outs = real(state, *args)
        return state, outs

    monkeypatch.setattr(api, "_pool_tick", frozen)
    result, numbers, _ = run_tiny(CELL)
    assert not result["correct"] and numbers["rows_bad_pct"] > 0


def test_half_the_batch_left_out_is_caught(monkeypatch):
    from repro.warehouse import SegmentStore
    real = SegmentStore.ingest_tick

    def half(self, traces, *, valid=None, **kw):
        keep = np.asarray(valid, bool).copy()
        keep[::2] = False
        return real(self, traces, valid=keep, **kw)

    monkeypatch.setattr(SegmentStore, "ingest_tick", half)
    result, numbers, _ = run_tiny(CELL)
    assert not result["correct"] and numbers["rows_missing"] > 0


def test_altered_standing_answer_is_caught(monkeypatch):
    from repro.warehouse import StandingQueries
    real = StandingQueries.answer

    def altered(self, handle):
        table, mask = real(self, handle)
        return ({k: (v * jnp.float32(1.001) if k in ("quality", "category")
                     else v) for k, v in table.items()}, mask)

    monkeypatch.setattr(StandingQueries, "answer", altered)
    result, numbers, _ = run_tiny(CELL)
    assert not result["correct"] and numbers["standing_gap"] > 1e-4


def test_altered_transform_result_is_caught(monkeypatch):
    from repro.warehouse import SegmentStore
    real = SegmentStore.ingest_tick

    def bumped(self, traces, *, quality, **kw):
        return real(self, traces, quality=quality.at[3].add(0.25), **kw)

    monkeypatch.setattr(SegmentStore, "ingest_tick", bumped)
    result, numbers, _ = run_tiny(CELL)
    assert not result["correct"] and numbers["rows_bad_pct"] > 0


@pytest.mark.parametrize("wrong", ["uniform", "classes_rolled"])
def test_wrong_replan_is_caught(monkeypatch, wrong):
    from repro.core import api
    real = api._pool_replan

    def replan(*args, **kw):
        plan = real(*args, **kw)
        if wrong == "uniform":
            return jnp.full_like(plan, 1.0 / plan.shape[-1])
        return jnp.roll(plan, 1, axis=1)

    monkeypatch.setattr(api, "_pool_replan", replan)
    result, numbers, _ = run_tiny(CELL)
    assert not result["correct"] and numbers["plan_bad"] > 0


def test_plan_check_accepts_lp_optima_and_no_other_plan():
    """Every plan the planner's solver gives, for random forecasts and
    budgets that bind or do not, passes the plan check; a plan that is
    no optimum for any forecast does not."""
    from bench import oracle
    from repro.core.planner import solve_lp_lagrangian
    rng = np.random.default_rng(3)
    C, K = 4, 9
    for _ in range(20):
        q = np.sort(rng.uniform(0.3, 1.0, (C, K)), axis=1)
        cost = np.sort(rng.uniform(0.1, 3.0, K))
        budget = float(rng.uniform(cost[0], cost[-1]))
        r = rng.dirichlet(np.ones(C))
        plan = np.asarray(solve_lp_lagrangian(
            jnp.asarray(q, jnp.float32), jnp.asarray(cost, jnp.float32),
            jnp.asarray(r, jnp.float32), jnp.float32(budget)))
        assert oracle.plans_bad([(0, plan[None])], q, cost, budget) == 0
        cheap = np.zeros((C, K), np.float32)
        cheap[:, 0] = 1.0
        wrong = [np.full((C, K), 1.0 / K, np.float32), cheap]
        assert oracle.plans_bad([(0, np.stack(wrong))], q, cost,
                                budget) == 2
