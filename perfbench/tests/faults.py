"""The faults a cell can have, planted UNDER a driver by wrapping its
``build``: the production entry and the drivers know nothing of them. Each is
a context manager; inside it a run drives the broken timed path.

    with faults.planted("half_batch"):
        line = run.execute(ctx, bench, None, None)
"""

import contextlib

import numpy as np

from perfbench.drivers import serve, train


def _unchanged_state(step_fn):
    import jax.numpy as jnp

    def same(state, batch, key):
        return state, {"loss": jnp.zeros((), jnp.float32)}
    same._cache_size = lambda: 1
    return same


def _half_batch(step_fn):
    """Half of the batch left out, the mean taken over the rest."""
    def half(state, batch, key):
        def fold(x):
            x = np.asarray(x)
            h = x.shape[1] // 2
            return np.concatenate([x[:, :h], x[:, :h]], axis=1)
        return step_fn(state, {k: fold(v) for k, v in batch.items()}, key)
    half._cache_size = step_fn._cache_size
    return half


def _altered_token(engine, vocab):
    """A token altered where it is produced: every decoded id + 1."""
    real = engine._dispatch

    def altered(kind, *a, **kw):
        res = real(kind, *a, **kw)
        if kind == "decode_step" and res is not None:
            out, bad = res
            res = ((np.asarray(out) + 1) % vocab, bad)
        return res

    engine._dispatch = altered


TRAIN = {"unchanged_state": _unchanged_state, "half_batch": _half_batch}
SERVE = ("altered_token",)


@contextlib.contextmanager
def planted(fault):
    """Break the timed path of whichever driver has ``fault``; None plants
    nothing."""
    real_train, real_serve = train.build, serve.build

    def train_build(ctx):
        trainer, loader, cfg = real_train(ctx)
        trainer.train_step = TRAIN[fault](trainer.train_step)
        return trainer, loader, cfg

    def serve_build(ctx, params):
        cfg, router, server, n = real_serve(ctx, params)
        for rep in router._replicas:
            _altered_token(rep.engine, cfg.vocab_size)
        return cfg, router, server, n

    if fault in TRAIN:
        train.build = train_build
    elif fault in SERVE:
        serve.build = serve_build
    elif fault is not None:
        raise ValueError(f"unknown fault {fault!r}")
    try:
        yield
    finally:
        train.build, serve.build = real_train, real_serve
