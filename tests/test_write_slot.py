"""The hot-swap's slot write: one jitted program that donates the stacked
(learner, carry) and overwrites one slot in place."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.population.engine import (Bucket, LocalDriver, PopulationEngine,
                                     SlotMeta)
from repro.population.objectives.lm import LMObjective

HP = {"learning_rate": 1e-3, "grad_clip": 1.0, "warmup_steps": 2,
      "loss_chunk": 16}


@pytest.fixture(scope="module")
def objective():
    return LMObjective("moonlight-16b-a3b", batch=1, seq=16)


def _bucket(objective, capacity=3):
    engine = PopulationEngine(objective, max_slots=capacity)
    return engine, Bucket(engine, 16, capacity, HP)


def _slot(objective, seed):
    return objective.init_slot_state(jax.random.PRNGKey(seed), HP)


def _fill(bucket, objective):
    """Distinct state in every slot, through the write under test."""
    for i in range(bucket.capacity):
        learner, carry = _slot(objective, 100 + i)
        bucket.write_slot(i, SlotMeta(i, HP, bucket.slot_ids[i]), learner,
                          carry, objective.traced_values(HP))


def _host(tree):
    return [np.asarray(x) for x in jax.tree.leaves(tree)]


def test_write_matches_at_set_bit_for_bit(objective):
    _, bucket = _bucket(objective)
    _fill(bucket, objective)
    stacked = (bucket.learner, bucket.carry)
    before = _host(stacked)
    learner, carry = _slot(objective, 7)
    expect = [a.at[1].set(v) for a, v in
              zip(jax.tree.leaves(stacked), jax.tree.leaves((learner,
                                                             carry)))]
    expect = [np.asarray(x) for x in expect]
    bucket.write_slot(1, SlotMeta(9, HP, bucket.slot_ids[1]), learner, carry,
                      objective.traced_values(HP))
    got = _host((bucket.learner, bucket.carry))
    assert len(got) == len(expect) > 10
    for g, e, b in zip(got, expect, before):
        assert g.dtype == e.dtype and g.shape == e.shape
        np.testing.assert_array_equal(g, e)
        # the other slots are as they were
        np.testing.assert_array_equal(np.delete(g, 1, 0), np.delete(b, 1, 0))


def test_one_compile_serves_every_slot(objective):
    engine, bucket = _bucket(objective, capacity=4)
    write = engine._write_slot
    learner, carry = _slot(objective, 3)
    sizes = []
    for i in range(4):
        bucket.write_slot(i, SlotMeta(i, HP, bucket.slot_ids[i]), learner,
                          carry, objective.traced_values(HP))
        sizes.append(write._cache_size())
    assert sizes[0] >= 1 and len(set(sizes)) == 1


def test_old_stack_is_donated(objective):
    _, bucket = _bucket(objective)
    old = jax.tree.leaves((bucket.learner, bucket.carry))
    learner, carry = _slot(objective, 4)
    bucket.write_slot(0, SlotMeta(0, HP, bucket.slot_ids[0]), learner, carry,
                      objective.traced_values(HP))
    assert all(x.is_deleted() for x in old)
    # the fresh slot is only read
    assert not any(x.is_deleted() for x in jax.tree.leaves((learner,
                                                            carry)))


def test_bucket_template_builds_no_slot(objective, monkeypatch):
    """The stacked shapes come from the objective's init traced
    abstractly: no slot's worth of device state is built for them."""
    seen = []
    init = objective.init_slot_state

    def spy(rng, hparams):
        out = init(rng, hparams)
        seen.append(all(isinstance(x, jax.core.Tracer)
                        for x in jax.tree.leaves(out)))
        return out
    monkeypatch.setattr(objective, "init_slot_state", spy)
    _, bucket = _bucket(objective, capacity=2)
    assert seen == [True]
    slot = jax.eval_shape(init, jax.random.PRNGKey(0), HP)
    for a, s in zip(jax.tree.leaves((bucket.learner, bucket.carry)),
                    jax.tree.leaves(slot)):
        assert a.shape == (2,) + s.shape and a.dtype == s.dtype
        assert not np.asarray(a).any()


def test_admitted_slots_train_after_in_place_writes(objective):
    """A whole search through admission, hot-swaps and steps: every trial
    completes with a finite metric."""
    from repro.core.hypertrick import RandomSearchPolicy
    from repro.core.search_space import SearchSpace
    from repro.core.service import OptimizationService
    policy = RandomSearchPolicy(SearchSpace({}), 4, 2,
                                configs=[dict(HP, learning_rate=lr)
                                         for lr in (1e-3, 2e-3, 5e-4, 1e-4)])
    engine = PopulationEngine(objective, max_slots=2, episodes_per_phase=2,
                              max_updates=2, seed=0)
    svc = OptimizationService(policy)
    records = engine.run(LocalDriver(svc))
    assert len(records) == 8
    assert all(np.isfinite(r[-1]) for r in records)
    assert {t.status.value for t in svc.db.trials.values()} == {"completed"}
