"""Faults planted in the timed path underneath a run, each of which the
cell's comparison has to catch. Each is a context manager that patches the
program while it is open.

    python -m chipbench.tests.faults <cell> <fault>   # one tiny run, on CPU

prints the run's result object; ``tests/test_chipbench_faults.py`` drives it.
"""
from __future__ import annotations

import contextlib
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np


@contextlib.contextmanager
def _patched(obj, name, make):
    orig = getattr(obj, name)
    setattr(obj, name, make(orig))
    try:
        yield
    finally:
        setattr(obj, name, orig)


def state_unchanged():
    """Every step returns the state it was given (its outputs otherwise)."""
    from repro.dmr.runner import MalleableRunner

    def make(orig):
        def step(self, state, i, *args):
            kept = jax.tree.map(jnp.copy, state)
            _, out = orig(self, state, i, *args)
            return kept, out
        return step
    return _patched(MalleableRunner, "step", make)


def half_batch():
    """The second half of every training batch is left out: its mask is
    zero, so the loss is the mean over the rest."""
    from repro.dmr.runner import MalleableRunner

    def make(orig):
        def step(self, state, i, batch=None, *args):
            batch = dict(batch)
            mask = np.array(batch["mask"])
            mask[mask.shape[0] // 2:] = 0.0
            batch["mask"] = mask
            return orig(self, state, i, batch, *args)
        return step
    return _patched(MalleableRunner, "step", make)


def no_exchange():
    """A resize moves no data between chips: every shard a chip did not
    already hold arrives as zeros."""
    import repro.dmr.runner as runner_mod

    def keep_first_shard(x):
        sharding = x.sharding
        if sharding.is_fully_replicated:
            return x
        first = x.addressable_shards[0]
        out = np.zeros(x.shape, x.dtype)
        out[first.index] = np.asarray(first.data)
        return jax.device_put(out, sharding)

    def make(orig):
        def redistribute(state, new_shardings, **kw):
            state, stats, per = orig(state, new_shardings, **kw)
            return jax.tree.map(keep_first_shard, state), stats, per
        return redistribute
    return _patched(runner_mod, "redistribute_tree", make)


def token_altered():
    """One served token is changed where it is produced, and the replica
    goes on from the changed one."""
    from repro.dmr.runner import MalleableRunner

    def make(orig):
        calls = {"n": 0}

        def step(self, state, i, *args):
            state, tok = orig(self, state, i, *args)
            calls["n"] += 1
            # two set-up steps, then the window's first batch: with the tiny
            # mix's 8 prompt tokens this is the third token it serves
            if calls["n"] == 12:
                tok = tok ^ 1
                state = {**state, "tok": tok}
            return state, tok
        return step
    return _patched(MalleableRunner, "step", make)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "no_exchange": no_exchange, "token_altered": token_altered}


def main(argv):
    from chipbench.tests import tiny
    cell, fault = argv
    if fault == "none":
        result = tiny.run(cell)
    else:
        with FAULTS[fault]():
            result = tiny.run(cell)
    print(json.dumps(result))


if __name__ == "__main__":
    main(sys.argv[1:])
