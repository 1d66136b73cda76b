"""An elastic training job that a scripted RMS resizes in a fixed cycle.

Traffic keys: ``global_batch`` x ``seq_len`` tokens per step, fixed across
layouts; ``warm_sizes``, one set-up step at each; ``cycle``, the sizes the
window visits in turn, ``steps_per_size`` steps at each, the resize coming
before the first; ``reference_steps`` steps that the reference follows in
blocks of ``reference_rows`` rows. Rows are uniform random tokens drawn from
the seed, every row different.

The system under test is ``lm_train_app`` under ``dmr.MalleableRunner`` and
``dmr.reconfig``, given the benchmark's weights and batches. Set-up builds
the one runner and state, drives them through the warm steps (every mesh's
step and every transfer compiles there) and hands both to the window.

End to end: ``train_tokens_per_s``, the tokens of every iteration of the
window (resize and step, each ending in ``block_until_ready``) over the
window's length. The window is whole cycles: it closes at the end of the
cycle running at ``--seconds``.
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import arith, harness, weights as W
from chipbench.kinds import common


def batch_at(seed: int, step: int, batch: int, seq: int,
             vocab: int) -> Dict[str, np.ndarray]:
    rng = np.random.default_rng([seed % (1 << 64), 3, step])
    t = rng.integers(0, vocab, (batch, seq + 1), dtype=np.int32)
    return {"tokens": t[:, :-1], "labels": t[:, 1:],
            "mask": np.ones((batch, seq), np.float32)}


def sizes(t: Dict, steps: int) -> List[int]:
    """Workers at each step: the warm sizes, then the cycle."""
    warm, cyc, k = t["warm_sizes"], t["cycle"], t["steps_per_size"]
    return [warm[s] if s < len(warm)
            else cyc[((s - len(warm)) // k) % len(cyc)] for s in range(steps)]


def run(ctx: harness.Context) -> harness.Outcome:
    from repro import dmr
    from repro.configs.base import ShapeConfig
    from repro.core.lm_app import lm_train_app
    from repro.models.train import TrainState
    from repro.optim.adamw import OptState

    c, t = ctx.config, ctx.traffic
    ref = common.reference(c)
    cfg = common.program_config(c, ref)
    B, S, V = t["global_batch"], t["seq_len"], c["vocab_size"]
    lay = ref.layout(c)
    f32 = jnp.float32
    key = W.base_key(ctx.seed)

    app = lm_train_app(cfg, ShapeConfig("bench", "train", S, B),
                       global_batch=B)

    @app.init
    def _init(mesh):
        def build(k):
            p = W.values(lay, k, f32)
            return TrainState(
                params=p,
                opt=OptState(mu=jax.tree.map(jnp.zeros_like, p),
                             nu=jax.tree.map(jnp.zeros_like, p),
                             count=jnp.zeros((), jnp.int32)),
                step=jnp.zeros((), jnp.int32),
                rng=jax.random.key_data(jax.random.fold_in(k, 1 << 30)),
                data_cursor=jnp.zeros((), jnp.int32))
        return jax.jit(build, out_shardings=app.state_shardings(mesh))(key)

    plan = sizes(t, 100_000)
    schedule = {s: n for s, n in enumerate(plan) if s and n != plan[s - 1]}
    runner = dmr.MalleableRunner(
        app, dmr.set_parameters(min(plan), max(plan), plan[0]), rms=schedule,
        devices=ctx.devices)
    state = runner.init()

    def iteration(state, step: int, log: List):
        t0 = time.perf_counter()
        events = len(runner.events)
        with ctx.span("bench.reconfig"):
            state = dmr.reconfig(runner, state, step)
        t1 = time.perf_counter()
        with ctx.span("bench.feed"):
            batch = batch_at(ctx.seed, step, B, S, V)
        with ctx.span("bench.step"):
            state, m = runner.step(state, step, batch)
            jax.block_until_ready((state, m))
        t2 = time.perf_counter()
        log.append({"step": step, "chips": runner.current,
                    "resized": len(runner.events) > events,
                    "reconfig_s": t1 - t0, "step_s": t2 - t1})
        return state, m

    # set-up: the warm steps through the window's own call and feed; the
    # readings the reference is compared with are taken on the way
    b1 = c["optimizer"]["b1"]
    grad_norms = jax.jit(lambda mu: arith.leaf_norms(mu) / (1 - b1))
    change_norms = jax.jit(lambda p, k: arith.leaf_norms(jax.tree.map(
        jnp.subtract, p, W.values(lay, k, f32))))
    n_ref = t["reference_steps"]
    prog: Dict[str, np.ndarray] = {}
    losses = []
    warm_log: List = []
    for step in range(len(t["warm_sizes"])):
        if step == n_ref:
            state = dmr.reconfig(runner, state, step)
            prog["change"] = np.asarray(change_norms(state.params, key))
        state, m = iteration(state, step, warm_log)
        if step < n_ref:
            losses.append(float(m["loss"]))
        if step == 0:
            prog["grad"] = np.asarray(grad_norms(state.opt.mu))
    prog["loss"] = np.asarray(losses)
    ctx.setup_done()

    log: List = []
    cycle_steps = len(t["cycle"]) * t["steps_per_size"]
    with ctx.window():
        start = time.perf_counter()
        start += ctx.start_trace()
        step = len(t["warm_sizes"])
        while True:
            for _ in range(cycle_steps):
                state, m = iteration(state, step, log)
                step += 1
            start += ctx.end_trace()
            if time.perf_counter() - start >= ctx.seconds:
                break
        window_s = time.perf_counter() - start

    peak = harness.memory_peak_bytes(ctx.devices)
    resizes = sum(r["resized"] for r in log)
    transfers = [e.transfer.seconds for e in runner.events[-resizes:]] \
        if resizes else []
    del state, m, runner, app

    tokens = B * S
    metrics = {"train_tokens_per_s": tokens * len(log) / window_s}
    checks = check(ctx, ref, prog)
    record = {"kind": "train", "window_s": window_s, "iterations": log,
              "flops_per_step": arith.train_flops(c, lay, tokens)["total"],
              "transfer_s": transfers,
              "peaks": ctx.peaks}
    return harness.Outcome(attempted=len(log), failed=0, metrics=metrics,
                           checks=checks, memory_peak_bytes=peak,
                           record=record)


def leaf_gaps(prog: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Each leaf's gap of norms, against the reference's norm of that leaf
    or of the median leaf, whichever is larger."""
    return np.abs(prog - ref) / np.maximum(ref, np.median(ref))


def moved(ref: Dict[str, np.ndarray]) -> np.ndarray:
    """The leaves whose reference gradient is not nought to rounding: at
    least a thousandth of the median leaf's."""
    return ref["grad_raw"] >= 1e-3 * np.median(ref["grad_raw"])


def readings(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray]
             ) -> Dict[str, float]:
    """The three numbers compared: the worst relative loss gap over the
    reference's steps, the worst leaf of the first gradient, and the worst
    leaf of the parameters' change over the leaves that :func:`moved`."""
    return {
        "loss_gap": float(np.max(np.abs(prog["loss"] - ref["loss"])
                                 / np.abs(ref["loss"]))),
        "grad_gap": float(leaf_gaps(prog["grad"], ref["grad"]).max()),
        "change_gap": float(leaf_gaps(prog["change"], ref["change"])
                            [moved(ref)].max()),
    }


def leaf_report(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
                layout) -> Dict:
    """Every leaf's gradient and change gap by name, and the leaves that
    the rule of :func:`moved` leaves out of the change: what tells a leaf
    that swings from seed to seed from one that is wrong."""
    paths = jax.tree_util.tree_flatten_with_path(layout, is_leaf=W.is_leaf)[0]
    names = [jax.tree_util.keystr(p) for p, _ in paths]
    keep = moved(ref)
    return {"grad": dict(zip(names, leaf_gaps(prog["grad"],
                                              ref["grad"]).tolist())),
            "change": dict(zip(names, leaf_gaps(prog["change"],
                                                ref["change"]).tolist())),
            "dropped": [n for n, k in zip(names, keep) if not k]}


def reference_readings(ctx: harness.Context, ref, **fault) -> Dict:
    """The reference through the first steps, its row blocks spread over
    the cell's chips."""
    c, t = ctx.config, ctx.traffic
    batches = [batch_at(ctx.seed, s, t["global_batch"], t["seq_len"],
                        c["vocab_size"]) for s in range(t["reference_steps"])]
    return ref.Trainer(c, t["reference_rows"], ctx.devices, **fault).run(
        ctx.seed, batches)


def check(ctx: harness.Context, ref, prog: Dict[str, np.ndarray]
          ) -> List[harness.Check]:
    """The numbers the configuration gives a limit; a number without one
    separates no control or fault from sound runs and is only logged."""
    base = reference_readings(ctx, ref)
    got = readings(prog, base)
    ctx.log({"readings": got,
             "leaves": leaf_report(prog, base, ref.layout(ctx.config))})
    limits = ctx.config["limits"]
    return [harness.Check(k, v, limits[k]) for k, v in got.items()
            if k in limits]
