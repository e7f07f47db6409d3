"""Faults planted under the timed path, to show that ``correct`` catches them.

Each is a context manager entered around the program's part of a run (the
step is traced inside it, so the fault is compiled into the step):

* ``unchanged_state``: the step returns the parameters and optimizer state
  it was given;
* ``half_batch``: the second half of each chip's rows is left out of the
  loss, whose mean is taken over the rest;
* ``no_exchange``: the all-to-all exchange between chips returns what it
  was given;
* ``no_grad_sync``: the step's per-leaf gradient sum over the replicated
  axes returns each chip's own gradient (its scalar loss sums stay).
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _patched(module, name, value):
    orig = getattr(module, name)
    setattr(module, name, value)
    try:
        yield
    finally:
        setattr(module, name, orig)


def unchanged_state():
    import repro.train.step as S
    orig = S.train_step_fn

    def step(params, opt_state, batch, step, sent=None, **kw):
        out = orig(params, opt_state, batch, step, sent, **kw)
        return (params, opt_state) + tuple(out[2:])
    return _patched(S, "train_step_fn", step)


def half_batch():
    import repro.train.step as S
    orig = S.train_step_fn

    def step(params, opt_state, batch, step, sent=None, **kw):
        lab = batch["labels"]
        batch = dict(batch, labels=lab.at[lab.shape[0] // 2:].set(-1))
        return orig(params, opt_state, batch, step, sent, **kw)
    return _patched(S, "train_step_fn", step)


def no_exchange():
    from repro.sharding import comm
    return _patched(comm, "all_to_all", lambda x, axes, **kw: x)


def no_grad_sync():
    import types
    import repro.train.step as S
    comm = S.comm

    def psum(x, axes, **kw):
        # in the step, only the gradient sync sums arrays with dimensions
        return x if x.ndim else comm.psum(x, axes, **kw)
    return _patched(S, "comm", types.SimpleNamespace(**dict(vars(comm), psum=psum)))


FAULTS = {"unchanged_state": unchanged_state, "half_batch": half_batch,
          "no_exchange": no_exchange, "no_grad_sync": no_grad_sync}
