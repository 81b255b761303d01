"""Faults planted under the timed path, to show that ``correct`` catches them.

Each is a context manager that breaks the program where it runs and puts
it back on exit.  ``bench/readings.py --faults`` reads them at a cell's own
size; ``tests/bench/`` runs them at a test's size.  The benchmark's own
runs never plant one.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _patched(module, name, wrap):
    orig = getattr(module, name)
    setattr(module, name, wrap(orig))
    try:
        yield
    finally:
        setattr(module, name, orig)


def answer_altered():
    """One frame's confidence changed where the fast pass produces it."""
    from repro.serving import engine

    def wrap(orig):
        def fast_pass(*a, **k):
            preds, conf = orig(*a, **k)
            return preds, conf.at[0].add(0.25)
        return fast_pass
    return _patched(engine, "fast_pass", wrap)


def half_left_out():
    """The compiled round serves only the first half of the streams."""
    from repro.serving import engine_jax as ej

    def wrap(orig):
        def simulate(spec, params, inputs, carry=None):
            S = inputs.valid.shape[1]
            valid = inputs.valid.at[:, S // 2:].set(False)
            return orig(spec, params, inputs._replace(valid=valid), carry)
        return simulate
    return _patched(ej, "simulate", wrap)


def state_unchanged():
    """Every round starts from the initial state: backlogs and bandwidth
    estimates never carry from one round to the next."""
    import jax
    import jax.numpy as jnp

    from repro.serving import engine_jax as ej

    def wrap(orig):
        def simulate(spec, params, inputs, carry=None):
            outs = [orig(spec, params, jax.tree.map(lambda x: x[r:r + 1], inputs))
                    for r in range(inputs.conf.shape[0])]
            ys = jax.tree.map(lambda *x: jnp.concatenate(x), *[o[1] for o in outs])
            return outs[-1][0], ys
        return simulate
    return _patched(ej, "simulate", wrap)


def wrong_rung():
    """Every offload is answered at another rung than the one planned: the
    compiled round reads the slow tier's answer of the next rung down (the
    lowest rung reads the highest)."""
    import jax.numpy as jnp

    from repro.serving import engine_jax as ej

    def wrap(orig):
        def simulate(spec, params, inputs, carry=None):
            slow_ok = jnp.roll(inputs.slow_ok, 1, axis=-1)
            return orig(spec, params, inputs._replace(slow_ok=slow_ok), carry)
        return simulate
    return _patched(ej, "simulate", wrap)


FAULTS = {f.__name__: f for f in (answer_altered, half_left_out, state_unchanged, wrong_rung)}
