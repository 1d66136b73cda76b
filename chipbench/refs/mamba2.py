"""Plain reference of a Mamba-2 language model (arXiv:2405.21060), float32,
with its loss, gradients and AdamW: what a training cell is compared with.

Per layer: ``x += mixer(rmsnorm(x))``. The mixer projects ``z``, ``x``,
``B``, ``C`` and ``dt`` from its input, runs a depthwise causal convolution
and SiLU over ``x``, ``B`` and ``C``, sets ``dt = softplus(dt + dt_bias)``
and ``A = -exp(A_log)``, applies the SSD transform (the paper's minimal
chunked form, one group of ``B``/``C`` shared by all heads) and the ``D``
skip, then ``rmsnorm(y * silu(z)) @ out_proj``. The output head is the
embedding table (tied); the softmax runs over every row of the table, as the
published model's does over its padded table. Departures from the
published block are the keys the configuration lists under ``departures``: no
convolution bias, and a residual stream in the compute dtype.

The weight tree has the layout the program takes, so that the benchmark's
generator can hand the program the numbers the reference makes itself.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W
from chipbench.arith import leaf_norms
from chipbench.refs import ops


def padded_vocab(c: Dict) -> int:
    m = c["pad_vocab_size_multiple"]
    return -(-c["vocab_size"] // m) * m


def dims(c: Dict):
    d = c["d_model"]
    di = c["expand"] * d
    return d, di, c["d_state"], di // c["headdim"], c["headdim"], c["d_conv"]


def layout(c: Dict):
    d, di, n, h, _, w = dims(c)
    L = c["n_layer"]
    i = c["init"]
    Leaf = W.Leaf
    std = i["std"]
    return {
        "embed": {"embedding": Leaf((padded_vocab(c), d), scale=std)},
        "layers": {
            "ln": {"scale": Leaf((d,), "ones", layers=L)},
            "ssm": {
                "A_log": Leaf((h,), "log_uniform", lo=i["A_min"],
                              hi=i["A_max"], layers=L),
                "D_skip": Leaf((h,), "ones", layers=L),
                "conv_B": Leaf((w, n), "uniform", scale=i["conv_bound"],
                               layers=L),
                "conv_C": Leaf((w, n), "uniform", scale=i["conv_bound"],
                               layers=L),
                "conv_x": Leaf((w, di), "uniform", scale=i["conv_bound"],
                               layers=L),
                "dt_bias": Leaf((h,), "dt_bias", lo=i["dt_min"],
                                hi=i["dt_max"], layers=L),
                "norm": Leaf((di,), "ones", layers=L),
                "w_B": Leaf((d, n), scale=std, layers=L),
                "w_C": Leaf((d, n), scale=std, layers=L),
                "w_dt": Leaf((d, h), scale=std, layers=L),
                "w_out": Leaf((di, d), scale=std / math.sqrt(2 * L),
                              layers=L),
                "w_x": Leaf((d, di), scale=std, layers=L),
                "w_z": Leaf((d, di), scale=std, layers=L),
            },
        },
        "ln_f": {"scale": Leaf((d,), "ones")},
    }


def program_fields(c: Dict) -> Dict:
    """The program's ``ArchConfig`` fields this configuration fixes."""
    return {"num_layers": c["n_layer"], "d_model": c["d_model"],
            "vocab_size": c["vocab_size"], "attention": "none",
            "tie_embeddings": c["tie_embeddings"],
            "norm_eps": c["norm_epsilon"], "dtype": c["dtype"]["compute"],
            "param_dtype": c["dtype"]["params"],
            "opt_moment_dtype": c["dtype"]["optimizer_moments"],
            "ssm.state_size": c["d_state"], "ssm.head_dim": c["headdim"],
            "ssm.expand": c["expand"], "ssm.conv_width": c["d_conv"],
            "ssm.chunk_size": c["chunk_size"]}


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------

def _segsum(a):
    """(..., T) -> (..., T, T): sum of a[j+1..i] below the diagonal, -inf
    above it."""
    T = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), seg, -jnp.inf)


def ssd(x, a, B, C, chunk: int):
    """The minimal SSD of the paper's listing. x: (b, S, h, p) already
    scaled by dt; a: (b, S, h) = dt * A; B, C: (b, S, n). Zero initial
    state."""
    b, S, h, p = x.shape
    c = S // chunk
    hp = ops.HIGHEST
    x = x.reshape(b, c, chunk, h, p)
    B = B.reshape(b, c, chunk, -1)
    C = C.reshape(b, c, chunk, -1)
    a = jnp.moveaxis(a.reshape(b, c, chunk, h), -1, 1)      # (b, h, c, l)
    acs = jnp.cumsum(a, axis=-1)
    Lm = jnp.exp(_segsum(a))                                 # (b,h,c,l,s)
    y_diag = jnp.einsum("bcln,bcsn,bhcls,bcshp->bclhp", C, B, Lm, x,
                        precision=hp)
    decay = jnp.exp(acs[..., -1:] - acs)                     # (b, h, c, l)
    states = jnp.einsum("bcln,bhcl,bclhp->bchpn", B, decay, x, precision=hp)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    decay_chunk = jnp.exp(_segsum(jnp.pad(acs[..., -1], ((0, 0), (0, 0),
                                                         (1, 0)))))
    states = jnp.einsum("bhzc,bchpn->bzhpn", decay_chunk, states,
                        precision=hp)[:, :-1]
    y_off = jnp.einsum("bcln,bchpn,bhcl->bclhp", C, states, jnp.exp(acs),
                       precision=hp)
    return (y_diag + y_off).reshape(b, S, h, p)


def _conv(x, w):
    """Depthwise causal convolution, no bias. x: (b, S, ch); w: (width, ch);
    ``w[-1]`` weighs the current position."""
    width = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(width))


def mixer(c: Dict, w, u, quant: Optional[str] = None, keep: int = 0):
    """One Mamba-2 mixer. ``keep`` > 0 plants the fault of a tensor-parallel
    layout whose all-reduce is left out: the output projection sums only the
    first ``keep`` channels of the inner width."""
    d, di, n, h, p, _ = dims(c)
    b, S, _ = u.shape

    def mm(t, m):
        return ops.matmul(t.reshape(-1, t.shape[-1]), m, quant).reshape(
            *t.shape[:-1], m.shape[-1])

    z, xs = mm(u, w["w_z"]), mm(u, w["w_x"])
    B, C, dt = mm(u, w["w_B"]), mm(u, w["w_C"]), mm(u, w["w_dt"])
    xs = jax.nn.silu(_conv(xs, w["conv_x"]))
    B = jax.nn.silu(_conv(B, w["conv_B"]))
    C = jax.nn.silu(_conv(C, w["conv_C"]))
    dt = jax.nn.softplus(dt + w["dt_bias"])
    A = -jnp.exp(w["A_log"])
    xh = xs.reshape(b, S, h, p)
    y = ssd(xh * dt[..., None], dt * A, B, C, c["chunk_size"])
    y = y + xh * w["D_skip"][:, None]
    y = y.reshape(b, S, di) * jax.nn.silu(z)
    y = ops.rmsnorm(y, w["norm"], c["norm_epsilon"])
    if keep:
        return mm(y[..., :keep], w["w_out"][:keep])
    return mm(y, w["w_out"])


def loss_sum(c: Dict, params, tokens, labels, quant: Optional[str] = None,
             keep: int = 0):
    """Summed cross-entropy of a block of rows (divide by the token count)."""
    eps = c["norm_epsilon"]
    table = params["embed"]["embedding"]
    x = table[tokens]

    @jax.checkpoint
    def body(x, lw):
        u = ops.rmsnorm(x, lw["ln"]["scale"], eps)
        return x + mixer(c, lw["ssm"], u, quant, keep), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    x = ops.rmsnorm(x, params["ln_f"]["scale"], eps)
    b, S, d = x.shape
    logits = ops.matmul(x.reshape(b * S, d), table.T, quant)
    logz = jax.nn.logsumexp(logits, axis=-1)
    got = jnp.take_along_axis(logits, labels.reshape(-1, 1), axis=-1)[:, 0]
    return jnp.sum(logz - got)


# ----------------------------------------------------------------------
# training: loss, clipped gradient and AdamW, as the configuration states
# ----------------------------------------------------------------------

def adamw(c: Dict, params, grads, mu, nu, count):
    """One AdamW update (``count``, a float, counts from 1); decay on leaves of two or
    more axes as stored, global-norm clipping first."""
    o = c["optimizer"]
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, o["clip_norm"] / (gnorm + 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    mu = jax.tree.map(lambda m, g: o["b1"] * m + (1 - o["b1"]) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: o["b2"] * v + (1 - o["b2"]) * g * g, nu,
                      grads)
    bc1 = 1 - o["b1"] ** count
    bc2 = 1 - o["b2"] ** count

    def upd(p, m, v):
        step = (m / bc1) / (jnp.sqrt(v / bc2) + o["eps"])
        if p.ndim >= o["decay_min_ndim"]:
            step = step + o["weight_decay"] * p
        return p - o["learning_rate"] * step

    return jax.tree.map(upd, params, mu, nu), mu, nu, grads


class Trainer:
    """The reference put through its steps, in blocks of ``rows`` rows so
    that it fits a chip at the cell's size. The blocks of a step are spread
    over ``devices`` (each holds a copy of the parameters) and their
    gradients summed on the first.

    ``quant`` / ``keep`` / ``rows_used`` run the control and the planted
    faults in the reference's place (``chipbench/calibrate.py``).
    """

    def __init__(self, c: Dict, rows: int, devices: List,
                 quant: Optional[str] = None, keep: int = 0,
                 rows_used: Optional[int] = None):
        self.c = c
        self.rows = rows
        self.devices = list(devices)
        self.rows_used = rows_used
        lay = layout(c)
        self._init = jax.jit(lambda k: W.values(lay, k, jnp.float32))

        def grad_block(params, tokens, labels):
            return jax.value_and_grad(
                lambda p: loss_sum(c, p, tokens, labels, quant, keep))(params)

        self._grad = jax.jit(grad_block)
        self._add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
        self._update = jax.jit(lambda p, g, m, v, n: adamw(c, p, g, m, v, n))
        self._norms = jax.jit(leaf_norms)
        self._change = jax.jit(lambda p, p0: leaf_norms(
            jax.tree.map(jnp.subtract, p, p0)))

    def _gradient(self, params, tokens, labels):
        """Summed loss and gradient of the rows, on the first device."""
        first = self.devices[0]
        copies = [params] + [jax.device_put(params, d)
                             for d in self.devices[1:]]
        parts = []
        for j, r in enumerate(range(0, tokens.shape[0], self.rows)):
            k = j % len(self.devices)
            put = lambda a: jax.device_put(a[r:r + self.rows],  # noqa: E731
                                           self.devices[k])
            parts.append(self._grad(copies[k], put(tokens), put(labels)))
        total, grads = None, None
        for l, g in parts:                       # dispatched; now gathered
            l, g = jax.device_put((l, g), first)
            total = l if total is None else total + l
            grads = g if grads is None else self._add(grads, g)
        return total, grads

    def run(self, seed: int, batches: List[Dict[str, np.ndarray]]) -> Dict:
        """Losses of each step, the first clipped gradient's leaf norms, the
        first raw gradient's leaf norms, and the leaf norms of the change of
        the parameters after all the steps."""
        with jax.default_device(self.devices[0]):
            params = self._init(W.base_key(seed))
            p0 = jax.tree.map(jnp.copy, params)
            mu = jax.tree.map(jnp.zeros_like, params)
            nu = jax.tree.map(jnp.zeros_like, params)
        losses, out = [], {}
        for i, batch in enumerate(batches, start=1):
            used = self.rows_used or batch["tokens"].shape[0]
            total, grads = self._gradient(params, batch["tokens"][:used],
                                          batch["labels"][:used])
            n_tok = used * batch["tokens"].shape[1]
            grads = jax.tree.map(lambda g: g / n_tok, grads)
            losses.append(float(total) / n_tok)
            if i == 1:
                out["grad_raw"] = np.asarray(self._norms(grads))
            params, mu, nu, clipped = self._update(params, grads, mu, nu,
                                                   jnp.float32(i))
            if i == 1:
                out["grad"] = np.asarray(self._norms(clipped))
            del grads, clipped
        out["loss"] = np.asarray(losses)
        out["change"] = np.asarray(self._change(params, p0))
        return out
