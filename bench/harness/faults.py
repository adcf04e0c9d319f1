"""Faults planted under the timed path, for the tests that show
``correct`` comes out false (and for calibration runs that read them on
the chip). Each takes what the harness built and returns it broken."""
from __future__ import annotations


def altered_token(eng, every: int = 7):
    """Serving: a token is altered where it is produced: every
    ``every``-th sampled token is replaced by its successor id."""
    sched = eng.sched
    orig = sched.append_token
    vocab = eng.model.cfg.vocab_size
    n = [0]

    def append_token(slot, token):
        n[0] += 1
        if n[0] % every == 0:
            token = (int(token) + 1) % vocab
        return orig(slot, token)

    sched.append_token = append_token


def unchanged_state(step):
    """Training: the step returns its state unchanged (it runs on copies
    and hands back the state it was given)."""
    import jax
    import jax.numpy as jnp

    def f(params, opt, batch):
        pc = jax.tree.map(jnp.copy, params)
        oc = jax.tree.map(jnp.copy, opt)
        _, _, m = step(pc, oc, batch)
        return params, opt, m

    return f


def half_batch(step):
    """Training: half of the batch is left out and the mean is taken
    over the rest."""
    def f(params, opt, batch):
        half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
        return step(params, opt, half)

    return f


SERVE = {"altered_token": altered_token}
TRAIN = {"unchanged_state": unchanged_state, "half_batch": half_batch}
