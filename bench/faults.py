"""Faults planted in the program under test, to show that ``correct``
catches them: each is a context manager that breaks the timed path where
the program builds it, and puts it back on exit.

* ``unchanged``: the step returns its parameters and optimizer state as
  they came in;
* ``half_batch``: the loss reads half of the batch, its mean taken over
  that half;
* ``exchange``: the TMP all-reduce between chips is left out;
* ``token``: the decode step's token is altered where it is produced.
"""
from __future__ import annotations

import contextlib
from unittest import mock


@contextlib.contextmanager
def unchanged():
    from repro.optim import adamw

    def apply_updates(params, grads, opt_state, cfg, **_):
        return params, opt_state, adamw.global_norm(grads)

    with mock.patch.object(adamw, "apply_updates", apply_updates):
        yield


@contextlib.contextmanager
def half_batch():
    from repro.models import lm
    build = lm.build_train_loss

    def build_half(*a, **kw):
        loss_fn, specs, in_specs = build(*a, **kw)

        def half(params, batch):
            return loss_fn(params, {k: v[: v.shape[0] // 2]
                                    for k, v in batch.items()})
        return half, specs, in_specs

    with mock.patch.object(lm, "build_train_loss", build_half):
        yield


@contextlib.contextmanager
def exchange():
    from repro.core import tmp

    with mock.patch.object(tmp, "tmp_reduce", lambda x, *a, **kw: x):
        yield


@contextlib.contextmanager
def token():
    from repro.models import lm
    build = lm.build_decode

    def build_altered(cfg, *a, **kw):
        fn, specs, st_specs = build(cfg, *a, **kw)

        def altered(*args):
            tok, state = fn(*args)
            return (tok + 1) % cfg.padded_vocab(), state
        return altered, specs, st_specs

    with mock.patch.object(lm, "build_decode", build_altered):
        yield


FAULTS = {"unchanged": unchanged, "half_batch": half_batch,
          "exchange": exchange, "token": token}
