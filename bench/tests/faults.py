"""Faults planted in the timed path, for the tests that see ``correct``
come out false: each takes the program's objective of a cell and returns
it with a broken step (and its own compile-cache key)."""
from __future__ import annotations

import jax


def _plant(obj, name, make_step):
    key = obj.cache_key()
    obj.cache_key = lambda: (key, name)
    obj.make_step = make_step
    return obj


def state_unchanged(obj):
    """A step that returns its state as it came."""
    return _plant(obj, "unchanged",
                  lambda structural, cap: (lambda learner, carry, *h:
                                           (learner, carry)))


def lm_half_batch(obj):
    """The LM loss over the first half of each sequence's positions."""
    from repro.models.model import forward
    from repro.optim.optimizers import apply_updates
    from repro.population.objectives.lm import _bigram_chain
    from repro.train.steps import lm_loss

    def make_step(structural, cap):
        cfg, half = obj.cfg, obj.seq // 2

        def one(learner, carry, lr, grad_clip, warmup_steps):
            params, opt_state = learner
            rng, k_start, k_choice = jax.random.split(carry["rng"], 3)
            chain = _bigram_chain(obj.table, k_start, k_choice, obj.batch,
                                  obj.seq)
            batch = {"tokens": chain[:, :-1], "labels": chain[:, 1:]}

            def loss_fn(p):
                h, _, _ = forward(cfg, p, batch, mode="train")
                return lm_loss(cfg, p, h[:, :half], batch["labels"][:, :half],
                               half)
            loss, grads = jax.value_and_grad(loss_fn)(params)
            params, opt_state, _ = apply_updates(
                obj.tc, params, grads, opt_state, lr=lr,
                grad_clip=grad_clip, warmup_steps=warmup_steps)
            return (params, opt_state), {"rng": rng, "n": carry["n"] + 1.0,
                                         "loss_sum": carry["loss_sum"] - loss}
        return one
    return _plant(obj, "half_batch", make_step)


def lm_loss_altered(obj):
    """The reported loss, the answer a trial's phase is judged by, off by
    a tenth where the step produces it."""
    good = obj.make_step

    def make_step(structural, cap):
        one = good(structural, cap)

        def altered(learner, carry, *hyper):
            learner, new = one(learner, carry, *hyper)
            step = new["loss_sum"] - carry["loss_sum"]
            return learner, dict(new,
                                 loss_sum=carry["loss_sum"] + 1.1 * step)
        return altered
    return _plant(obj, "loss_altered", make_step)
