"""A decode replica serving a closed loop of static batches.

Traffic keys: ``batch`` sequences per batch, ``cache_len`` cache positions,
``prompt_len`` prompt tokens fed one per step (prefill by decode),
``gen_len`` greedy tokens per sequence, ``warm_steps`` set-up steps,
``trace_steps`` the steps ``[first, end)`` of the window's first batch that
a traced run traces, ``check_sequences`` finished sequences compared with
the reference after the window. The next batch starts when the last one
ends; prompts are drawn from the seed.

The system under test is ``make_decode_app`` under ``dmr.MalleableRunner``
on one mesh, given the benchmark's weights. Every step ends in a host read
of the batch's tokens, as a server streams them, and that read stamps each
token.

End to end: ``decode_tokens_per_s`` (tokens served in the window over its
length) and ``itl_p95_ms`` (95th percentile of the gaps between a
sequence's consecutive tokens, over every sequence). The window is whole
batches: it closes when the batch running at ``--seconds`` ends.
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness, weights as W
from chipbench.kinds import common


#: the batch index of the set-up's warm batch, apart from the window's
WARM = 1 << 32


def prompts(seed: int, batch_index: int, batch: int, length: int,
            vocab: int) -> np.ndarray:
    rng = np.random.default_rng([seed % (1 << 64), 1, batch_index])
    return rng.integers(0, vocab, (batch, length), dtype=np.int32)


def run(ctx: harness.Context) -> harness.Outcome:
    from repro import dmr
    from repro.serve.replica import make_decode_app

    c, t = ctx.config, ctx.traffic
    ref = common.reference(c)
    cfg = common.program_config(c, ref)
    B, P, G = t["batch"], t["prompt_len"], t["gen_len"]
    steps = P + G - 1                     # the last prompt step serves token 1
    if steps > t["cache_len"]:
        raise ValueError(f"{steps} positions do not fit a cache of "
                         f"{t['cache_len']}")
    dtype = jnp.dtype(c["dtype"]["weights"])
    lay = ref.layout(c)

    app = make_decode_app(cfg, batch=B, cache_len=t["cache_len"])
    kv_shape = (c["num_hidden_layers"], B, t["cache_len"],
                c["num_key_value_heads"], c["head_dim"])

    @app.init
    def _init(mesh):
        ss = app.state_shardings(mesh)
        params = W.make(lay, ctx.seed, dtype, ss["params"])
        zeros = jax.jit(lambda: {"k": jnp.zeros(kv_shape, cfg.dtype),
                                 "v": jnp.zeros(kv_shape, cfg.dtype)},
                        out_shardings=ss["cache"]["layers"])
        return {"params": params, "cache": {"layers": zeros()},
                "tok": jax.device_put(np.zeros((B, 1), np.int32), ss["tok"]),
                "pos": jax.device_put(np.int32(0), ss["pos"])}

    runner = dmr.MalleableRunner(app, dmr.set_parameters(1, 1, 1), rms={},
                                 devices=ctx.devices[:1])
    state = runner.init()
    pos_sharding = state["pos"].sharding
    vocab = c["vocab_size"]

    def serve_batch(state, b: int, log: List, steps: int = steps,
                    trace=None):
        """One batch from its first prompt token to its last served token
        (or its first ``steps`` steps), tracing the steps ``trace`` names;
        returns the state, the (B, G) served tokens and the seconds the
        profiler took to start and stop."""
        with ctx.span("bench.batch_reset"):
            state = {**state, "pos": jax.device_put(np.int32(0),
                                                    pos_sharding)}
            prompt = prompts(ctx.seed, b, B, P, vocab)
        served = np.empty((B, G), np.int32)
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
                host = np.asarray(tok)[:, 0]
            t1 = time.perf_counter()
            if i >= P - 1:
                served[:, i - P + 1] = host
            log.append((b, i, i < P, i, t0, t1))
        if trace:
            paused += ctx.end_trace()
        return state, served, paused

    # set-up: prompt steps after a batch reset run every program and
    # transfer of the window (a decode step runs the same program, unfed)
    state, _, _ = serve_batch(state, WARM, [], steps=t["warm_steps"])
    ctx.setup_done()

    traced = t["trace_steps"]
    log: List = []
    batches: Dict[int, np.ndarray] = {}
    with ctx.window():
        start = time.perf_counter()
        b = 0
        while True:
            state, batches[b], paused = serve_batch(
                state, b, log, trace=traced if b == 0 else None)
            start += paused
            b += 1
            if time.perf_counter() - start >= ctx.seconds:
                break
        window_s = time.perf_counter() - start

    peak = harness.memory_peak_bytes(ctx.devices[:1])
    del state, runner, app

    # inter-token gaps: every sequence of a batch shares its steps' clocks
    gaps, served_n, prompt_s = [], 0, 0.0
    prev_end = {}
    for (bi, i, is_prompt, _pos, t0, t1) in log:
        if is_prompt:
            prompt_s += t1 - t0
        if i >= P - 1:
            served_n += B
            if i >= P:
                gaps.extend([t1 - prev_end[bi]] * B)
            prev_end[bi] = t1
    metrics = {"decode_tokens_per_s": served_n / window_s,
               "itl_p95_ms": float(np.percentile(gaps, 95)) * 1e3}

    checks = check(ctx, ref, batches)
    peaks = ctx.peaks
    record = {"kind": "decode", "window_s": window_s, "steps": log,
              "batch": B, "prompt_len": P, "prompt_s": prompt_s,
              "served": batches,
              # the traced steps' least times, in order (position = step)
              "traced_least_s": [common.decode_least_s(c, B, pos, peaks)
                                 for pos in range(*traced)],
              "peaks": peaks}
    return harness.Outcome(attempted=len(batches) * B, failed=0,
                           metrics=metrics, checks=checks,
                           memory_peak_bytes=peak, record=record)


def check(ctx: harness.Context, ref, batches: Dict[int, np.ndarray],
          control: bool = False) -> List[harness.Check]:
    """The widest gap by which a served token's logit lies below the
    reference's best, over a sample of finished sequences drawn from the
    seed. ``control``: the int8 control's first token at each position in
    the served token's place (``chipbench/calibrate.py``)."""
    c, t = ctx.config, ctx.traffic
    P, G, B = t["prompt_len"], t["gen_len"], t["batch"]
    n = t["check_sequences"]
    rng = np.random.default_rng([ctx.seed % (1 << 64), 2])
    picks = sorted(rng.choice(len(batches) * B, size=min(n, len(batches) * B),
                              replace=False))
    toks, served = [], []
    for k in picks:
        b, row = divmod(int(k), B)
        prompt = prompts(ctx.seed, b, B, P, c["vocab_size"])[row]
        out = batches[b][row]
        toks.append(np.concatenate([prompt, out[:-1]]))
        served.append(out)
    toks, served = np.stack(toks), np.stack(served)
    fn = jax.jit(lambda k, x, s: ref.served_gaps(c, k, x, s, P - 1,
                                                 control))
    with jax.default_device(ctx.devices[0]):
        gaps = np.asarray(fn(W.base_key(ctx.seed), toks, served))
    return [harness.Check("max_logit_gap", float(gaps.max()),
                          c["limits"]["max_logit_gap"])]
