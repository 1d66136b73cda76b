"""A decode replica of an interleaved Mamba-2 / attention hybrid serving a
closed loop of static batches, as ``decode_static`` serves a dense decoder.

Traffic keys as ``decode_static``'s. The system under test is
``make_decode_app`` under ``dmr.MalleableRunner`` on one chip, given the
benchmark's weights; its cache is the program's own ``init_cache``: each
Mamba layer's float32 state and conv windows and each attention layer's K
and V, stacked by kind. End to end as ``decode_static``; correctness compares
the served tokens with the reference by the widest and the mean gap
(``check``); the traced steps' least times come from
``chipbench/arith_hybrid.py``.
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import arith_hybrid, harness, weights as W
from chipbench.kinds import common, decode_static

#: the batch index of the set-up's warm batch, apart from the window's
WARM = decode_static.WARM
#: the window's slowest steps that a run logs
SLOWEST = 5


def run(ctx: harness.Context) -> harness.Outcome:
    from repro import dmr
    from repro.models import model as M
    from repro.serve.replica import make_decode_app

    c, t = ctx.config, ctx.traffic
    ref = common.reference(c)
    cfg = common.program_config(c, ref)
    B, P, G, S = t["batch"], t["prompt_len"], t["gen_len"], t["cache_len"]
    steps = P + G - 1                     # the last prompt step serves token 1
    if steps > S:
        raise ValueError(f"{steps} positions do not fit a cache of {S}")
    lay = ref.layout(c)
    app = make_decode_app(cfg, batch=B, cache_len=S)

    @app.init
    def _init(mesh):
        ss = app.state_shardings(mesh)
        cache = jax.jit(lambda: M.init_cache(cfg, B, S),
                        out_shardings=ss["cache"])
        dtype = jnp.dtype(c["dtype"]["weights"])
        return {"params": W.make(lay, ctx.seed, dtype, ss["params"]),
                "cache": cache(),
                "tok": jax.device_put(np.zeros((B, 1), np.int32), ss["tok"]),
                "pos": jax.device_put(np.int32(0), ss["pos"])}

    runner = dmr.MalleableRunner(app, dmr.set_parameters(1, 1, 1), rms={},
                                 devices=ctx.devices[:1])
    state = runner.init()

    def serve_batch(b: int, log: List, steps: int = steps, trace=None):
        """Batch ``b`` for ``steps`` steps, tracing the steps ``trace``
        names. Each step ends in a host read of the batch's tokens, which
        stamps them. Returns the (B, steps) tokens of every step and the
        seconds the profiler took to start and stop."""
        nonlocal state
        with ctx.span("bench.batch_reset"):
            state = {**state, "pos": jax.device_put(np.int32(0),
                                                    state["pos"].sharding)}
            prompt = decode_static.prompts(ctx.seed, b, B, P,
                                           c["vocab_size"])
        tokens = np.empty((B, steps), np.int32)
        paused = 0.0
        for i in range(steps):
            if trace and i == trace[0]:
                paused += ctx.start_trace()
            if trace and i == trace[1]:
                paused += ctx.end_trace()
            t0 = time.perf_counter()
            state = dmr.reconfig(runner, state, i)
            feed = prompt[:, i] if i < P else None
            with ctx.span("bench.step"):
                state, tok = runner.step(state, i, feed)
            with ctx.span("bench.token_sync"):
                tokens[:, i] = np.asarray(tok)[:, 0]
            log.append((b, i, i < P, i, t0, time.perf_counter()))
        if trace:
            paused += ctx.end_trace()
        return tokens, paused

    # set-up: prompt steps after a batch reset run every program and
    # transfer of the window (a decode step runs the same program, unfed)
    serve_batch(WARM, [], steps=t["warm_steps"])
    ctx.setup_done()

    traced = t["trace_steps"]
    log: List = []
    batches: Dict[int, np.ndarray] = {}
    with ctx.window():
        start = time.perf_counter()
        b = 0
        while True:
            tokens, paused = serve_batch(b, log,
                                         trace=traced if b == 0 else None)
            batches[b] = tokens[:, P - 1:P - 1 + G]
            start += paused
            b += 1
            if time.perf_counter() - start >= ctx.seconds:
                break
        window_s = time.perf_counter() - start
    peak = harness.memory_peak_bytes(ctx.devices[:1])
    del state, runner, app

    # inter-token gaps: every sequence of a batch shares its steps' clocks
    gaps, served_n, prompt_s, stepped = [], 0, 0.0, 0.0
    prev_end = {}
    for (bi, i, is_prompt, _pos, t0, t1) in log:
        stepped += t1 - t0
        if is_prompt:
            prompt_s += t1 - t0
        if i >= P - 1:
            served_n += B
            if i >= P:
                gaps.extend([t1 - prev_end[bi]] * B)
            prev_end[bi] = t1
    metrics = {"decode_tokens_per_s": served_n / window_s,
               "itl_p95_ms": float(np.percentile(gaps, 95)) * 1e3}
    # where a window longer at the same step times went: its slowest steps,
    # and its time outside every step (batch resets, the profiler's)
    slowest = sorted(log, key=lambda s: s[4] - s[5])[:SLOWEST]
    ctx.log({"window_s": window_s, "outside_steps_s": window_s - stepped,
             "slowest_steps_ms": [[b, i, (t1 - t0) * 1e3]
                                  for b, i, _, _, t0, t1 in slowest]})

    checks = check(ctx, ref, batches)
    record = {"kind": "decode", "window_s": window_s, "steps": log,
              "batch": B, "prompt_len": P, "prompt_s": prompt_s,
              "served": batches, "peaks": ctx.peaks,
              # the traced steps' least times, in order (position = step)
              "traced_least_s": [
                  arith_hybrid.decode_least_s(c, B, pos, ctx.peaks)
                  for pos in range(*traced)]}
    return harness.Outcome(attempted=len(batches) * B, failed=0,
                           metrics=metrics, checks=checks,
                           memory_peak_bytes=peak, record=record)


def check(ctx: harness.Context, ref, batches: Dict[int, np.ndarray],
          control: bool = False) -> List[harness.Check]:
    """``decode_static``'s comparison over the same sample of finished
    sequences, with the mean gap beside the widest: each served token's
    logit below the reference's best, ``max_logit_gap`` the widest and
    ``mean_logit_gap`` the mean over every token compared. ``control``:
    the int8 control's first token at each position in the served token's
    place (``chipbench/calibrate.py``).

    With Granite's logits divided by 8 and random weights, the best two
    logits of a position lie close, so a lower precision moves few tokens
    and the widest gap alone is an extreme of a few; the mean counts
    every token moved and by how much."""
    c, t = ctx.config, ctx.traffic
    P, B = t["prompt_len"], t["batch"]
    rng = np.random.default_rng([ctx.seed % (1 << 64), 2])
    picks = sorted(rng.choice(len(batches) * B,
                              size=min(t["check_sequences"],
                                       len(batches) * B), replace=False))
    toks, served = [], []
    for k in picks:
        b, row = divmod(int(k), B)
        prompt = decode_static.prompts(ctx.seed, b, B, P,
                                       c["vocab_size"])[row]
        toks.append(np.concatenate([prompt, batches[b][row][:-1]]))
        served.append(batches[b][row])
    fn = jax.jit(lambda k, x, s: ref.served_gaps(c, k, x, s, P - 1,
                                                 control))
    with jax.default_device(ctx.devices[0]):
        gaps = np.asarray(fn(W.base_key(ctx.seed), np.stack(toks),
                             np.stack(served)))
    limits = c["limits"]
    return [harness.Check("max_logit_gap", float(gaps.max()),
                          limits["max_logit_gap"]),
            harness.Check("mean_logit_gap", float(gaps.mean()),
                          limits["mean_logit_gap"])]
