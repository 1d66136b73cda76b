"""Plain reference of IBM's Granite-4.0-H hybrid decoder (Hugging Face
``granitemoehybrid`` with no experts), float32, no cache and no batching.

Per layer: ``x += r * mixer(rmsnorm(x))``, ``x += r * mlp(rmsnorm(x))``,
the mixer of each layer named by ``layer_types``. A Mamba-2 mixer projects
``z``, ``x``, ``B``, ``C`` and ``dt`` (one group, no bias), runs a depthwise
causal convolution with a bias and SiLU over ``x``, ``B`` and ``C``, sets
``dt = softplus(dt + dt_bias)`` and ``A = -exp(A_log)``, applies the SSD
transform (``refs/mamba2.py``'s ``ssd`` over positions padded to a whole
chunk) and the ``D`` skip, then ``rmsnorm(y * silu(z)) @ out_proj``. An
attention mixer is causal softmax over grouped-query heads with no
positional encoding (``position_embedding_type: nope``), its scores scaled
by ``attention_multiplier``. The MLP is SwiGLU of
``shared_intermediate_size``. The embedding is multiplied by
``embedding_multiplier``, each residual branch by ``residual_multiplier``,
and the logits, over the tied table, divided by ``logits_scaling``.

The weight tree has the layout the program takes, so that the benchmark's
generator (``chipbench/weights.py``) hands the program the numbers the
reference makes for itself from the seed, layer by layer.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from chipbench import weights as W
from chipbench.refs import ops
from chipbench.refs.mamba2 import _conv, ssd

KINDS = ("mamba", "attention")


def dims(c: Dict):
    d = c["hidden_size"]
    di = c["mamba_expand"] * d
    return d, di, c["mamba_d_state"], c["mamba_n_heads"], c["mamba_d_head"]


def counts(c: Dict) -> Dict[str, int]:
    """Layers of each mixer kind."""
    return {k: c["layer_types"].count(k) for k in KINDS}


def period(c: Dict):
    """The shortest run of ``layer_types`` that repeats over the depth."""
    kinds = c["layer_types"]
    for n in range(1, len(kinds) + 1):
        if len(kinds) % n == 0 and kinds == kinds[:n] * (len(kinds) // n):
            return kinds[:n]


def layout(c: Dict):
    d, di, n, h, _ = dims(c)
    a, kv, hd = c["num_attention_heads"], c["num_key_value_heads"], \
        c["head_dim"]
    f, w = c["shared_intermediate_size"], c["mamba_d_conv"]
    i, std = c["init"], c["initializer_range"]
    Lm, La = counts(c)["mamba"], counts(c)["attention"]
    L = len(c["layer_types"])
    Leaf = W.Leaf

    def conv(shape):
        return Leaf(shape, "uniform", scale=i["conv_bound"], layers=Lm)

    return {
        "embed": {"embedding": Leaf((c["vocab_size"], d), scale=std)},
        "layers": {
            "attention": {
                "attn": {"wk": Leaf((d, kv, hd), scale=std, layers=La),
                         "wo": Leaf((a, hd, d), scale=std, layers=La),
                         "wq": Leaf((d, a, hd), scale=std, layers=La),
                         "wv": Leaf((d, kv, hd), scale=std, layers=La)},
                "ln": {"scale": Leaf((d,), "ones", layers=La)}},
            "mamba": {
                "ln": {"scale": Leaf((d,), "ones", layers=Lm)},
                "ssm": {
                    "A_log": Leaf((h,), "log_uniform", lo=i["A_min"],
                                  hi=i["A_max"], layers=Lm),
                    "D_skip": Leaf((h,), "ones", layers=Lm),
                    "conv_B": conv((w, n)), "conv_B_bias": conv((n,)),
                    "conv_C": conv((w, n)), "conv_C_bias": conv((n,)),
                    "conv_x": conv((w, di)), "conv_x_bias": conv((di,)),
                    "dt_bias": Leaf((h,), "dt_bias", lo=i["dt_min"],
                                    hi=i["dt_max"], layers=Lm),
                    "norm": Leaf((di,), "ones", layers=Lm),
                    "w_B": Leaf((d, n), scale=std, layers=Lm),
                    "w_C": Leaf((d, n), scale=std, layers=Lm),
                    "w_dt": Leaf((d, h), scale=std, layers=Lm),
                    "w_out": Leaf((di, d), scale=std, layers=Lm),
                    "w_x": Leaf((d, di), scale=std, layers=Lm),
                    "w_z": Leaf((d, di), scale=std, layers=Lm)}},
            "mlp": {
                "ln": {"scale": Leaf((d,), "ones", layers=L)},
                "mlp": {"wi_gate": Leaf((d, f), scale=std, layers=L),
                        "wi_up": Leaf((d, f), scale=std, layers=L),
                        "wo": Leaf((f, d), scale=std, layers=L)}},
        },
        "ln_f": {"scale": Leaf((d,), "ones")},
    }


def program_fields(c: Dict) -> Dict:
    """The program's ``ArchConfig`` fields this configuration fixes."""
    return {"num_layers": c["num_hidden_layers"], "d_model": c["hidden_size"],
            "num_heads": c["num_attention_heads"],
            "num_kv_heads": c["num_key_value_heads"],
            "head_dim": c["head_dim"], "d_ff": c["shared_intermediate_size"],
            "vocab_size": c["vocab_size"], "norm_eps": c["rms_norm_eps"],
            "tie_embeddings": c["tie_word_embeddings"],
            "attention": "full", "dtype": c["dtype"]["compute"],
            "layer_types": tuple(c["layer_types"]),
            "position_embedding": c["position_embedding_type"],
            "embedding_multiplier": c["embedding_multiplier"],
            "attention_multiplier": c["attention_multiplier"],
            "residual_multiplier": c["residual_multiplier"],
            "logits_scaling": c["logits_scaling"],
            "ssm_num_heads": c["mamba_n_heads"],
            "ssm.state_size": c["mamba_d_state"],
            "ssm.head_dim": c["mamba_d_head"],
            "ssm.expand": c["mamba_expand"],
            "ssm.conv_width": c["mamba_d_conv"],
            "ssm.chunk_size": c["mamba_chunk_size"],
            "ssm.conv_bias": c["mamba_conv_bias"],
            "ssm.state_dtype": c["dtype"]["ssm_state"]}


def _mm(t, m, quant):
    return ops.matmul(t.reshape(-1, t.shape[-1]), m, quant).reshape(
        *t.shape[:-1], m.shape[-1])


def mamba(c: Dict, w, u, quant: Optional[str] = None):
    """One Mamba-2 mixer over ``u`` (n, S, d); S a whole number of chunks."""
    d, di, n, h, p = dims(c)
    b, S, _ = u.shape
    z, xs = _mm(u, w["w_z"], quant), _mm(u, w["w_x"], quant)
    B, C = _mm(u, w["w_B"], quant), _mm(u, w["w_C"], quant)
    dt = _mm(u, w["w_dt"], quant)
    xs = jax.nn.silu(_conv(xs, w["conv_x"]) + w["conv_x_bias"])
    B = jax.nn.silu(_conv(B, w["conv_B"]) + w["conv_B_bias"])
    C = jax.nn.silu(_conv(C, w["conv_C"]) + w["conv_C_bias"])
    dt = jax.nn.softplus(dt + w["dt_bias"])
    A = -jnp.exp(w["A_log"])
    xh = xs.reshape(b, S, h, p)
    y = ssd(xh * dt[..., None], dt * A, B, C, c["mamba_chunk_size"])
    y = y + xh * w["D_skip"][:, None]
    y = y.reshape(b, S, di) * jax.nn.silu(z)
    y = ops.rmsnorm(y, w["norm"], c["rms_norm_eps"])
    return _mm(y, w["w_out"], quant)


def attention(c: Dict, w, u, quant: Optional[str] = None):
    """Causal grouped-query attention with no positional encoding."""
    n, S, d = u.shape
    h, kv, hd = c["num_attention_heads"], c["num_key_value_heads"], \
        c["head_dim"]
    q = _mm(u, w["wq"].reshape(d, h * hd), quant).reshape(n, S, h, hd)
    k = _mm(u, w["wk"].reshape(d, kv * hd), quant).reshape(n, S, kv, hd)
    v = _mm(u, w["wv"].reshape(d, kv * hd), quant).reshape(n, S, kv, hd)
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v, h // kv, axis=2)
    s = jnp.einsum("nqhd,nkhd->nhqk", q, k, precision=ops.HIGHEST) \
        * c["attention_multiplier"]
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(s, axis=-1), v,
                   precision=ops.HIGHEST)
    return _mm(o.reshape(n, S, h * hd), w["wo"].reshape(h * hd, d), quant)


def mlp(w, u, quant: Optional[str] = None):
    return _mm(jax.nn.silu(_mm(u, w["wi_gate"], quant))
               * _mm(u, w["wi_up"], quant), w["wo"], quant)


def layer(c: Dict, kind: str, mixer_w, mlp_w, x, quant: Optional[str] = None):
    eps, r = c["rms_norm_eps"], c["residual_multiplier"]
    u = ops.rmsnorm(x, mixer_w["ln"]["scale"], eps)
    if kind == "mamba":
        x = x + r * mamba(c, mixer_w["ssm"], u, quant)
    else:
        x = x + r * attention(c, mixer_w["attn"], u, quant)
    u = ops.rmsnorm(x, mlp_w["ln"]["scale"], eps)
    return x + r * mlp(mlp_w["mlp"], u, quant)


def logits(c: Dict, key, tokens, weight_dtype, quant: Optional[str] = None):
    """Logits over the vocabulary at every position of ``tokens`` (n, S),
    the weights made layer by layer from ``key`` (``weights.base_key``)."""
    lay = layout(c)
    keys = W.leaf_keys(lay, key)

    def made(tree_lay, tree_keys, index=None):
        def one(leaf, k):
            v = W.leaf_value(leaf, k, weight_dtype) if index is None \
                else W.layer_value(leaf, k, index, weight_dtype)
            return v.astype(jnp.float32)
        return jax.tree.map(one, tree_lay, tree_keys, is_leaf=W.is_leaf)

    def stack(kind, index):
        return made(lay["layers"][kind], keys["layers"][kind], index)

    n, S = tokens.shape
    chunk = c["mamba_chunk_size"]
    padded = jnp.pad(tokens, ((0, 0), (0, -S % chunk)))
    table = made(lay["embed"], keys["embed"])["embedding"]
    x = table[padded] * c["embedding_multiplier"]
    per = period(c)
    per_kind = {k: per.count(k) for k in KINDS}
    # the period as runs of one kind, each scanned: one layer's program
    # per run (a body that unrolled the period would compile ten)
    runs, seen = [], dict.fromkeys(KINDS, 0)
    for i, kind in enumerate(per):
        if runs and runs[-1][0] == kind:
            runs[-1][3] += 1
        else:
            runs.append([kind, seen[kind], i, 1])
        seen[kind] += 1

    def body(x, p):
        for kind, first, start, n in runs:
            def one(x, i, kind=kind, first=first, start=start):
                return layer(c, kind,
                             stack(kind, p * per_kind[kind] + first + i),
                             stack("mlp", p * len(per) + start + i), x,
                             quant), None
            x, _ = jax.lax.scan(one, x, jnp.arange(n))
        return x, None

    x, _ = jax.lax.scan(body, x, jnp.arange(len(c["layer_types"])
                                           // len(per)))
    x = ops.rmsnorm(x[:, :S], made(lay["ln_f"], keys["ln_f"])["scale"],
                    c["rms_norm_eps"])
    d = x.shape[-1]
    out = ops.matmul(x.reshape(n * S, d), table.T, quant).reshape(n, S, -1)
    return out / c["logits_scaling"]


def served_gaps(c: Dict, key, tokens, served, first: int,
                control: bool = False):
    """How far below the reference's best logit each served token lies.

    ``tokens`` (n, S): prompt and served tokens as the program was fed them;
    ``served`` (n, T): the tokens it served, ``served[:, t]`` read at
    position ``first + t``. With ``control``, the token the int8 control puts
    first at each position takes the served token's place.
    Returns (n, T) gaps, 0 where the served token is the reference's best.
    """
    weight_dtype = jnp.dtype(c["dtype"]["weights"])
    T = served.shape[1]
    ref = logits(c, key, tokens, weight_dtype)[:, first:first + T]
    if control:
        ctl = logits(c, key, tokens, weight_dtype, "int8")
        served = jnp.argmax(ctl[:, first:first + T], axis=-1)
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, served[..., None], axis=-1)[..., 0]
    return best - got
