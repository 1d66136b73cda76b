"""Plain reference of a dense decoder with grouped-query attention (Llama /
Granite-3 layout), float32, no cache and no batching tricks.

Per layer: ``x += attn(rmsnorm(x))``, ``x += mlp(rmsnorm(x))``; attention is
causal softmax over rotary queries and keys, query head ``i`` reading key
and value head ``i // (heads / kv_heads)``; the MLP is SwiGLU; the output
head is the embedding table (tied). Departures from the published Granite
block are the multipliers the configuration lists under ``departures``: the
embedding, residual and logit multipliers are 1 and the attention scale is
``head_dim ** -0.5``.

The weight tree has the layout the program takes, so that the benchmark's
generator (``chipbench/weights.py``) can hand the program the same numbers
the reference makes for itself from the seed, layer by layer.
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from chipbench import weights as W
from chipbench.refs import ops


def padded_vocab(c: Dict) -> int:
    m = c["embedding_rows_multiple"]
    return -(-c["vocab_size"] // m) * m


def layout(c: Dict):
    d, h, kv = c["hidden_size"], c["num_attention_heads"], \
        c["num_key_value_heads"]
    hd, f, L = c["head_dim"], c["intermediate_size"], c["num_hidden_layers"]
    std = c["initializer_range"]
    Leaf = W.Leaf
    return {
        "embed": {"embedding": Leaf((padded_vocab(c), d), scale=std)},
        "layers": {
            "attn": {"wk": Leaf((d, kv, hd), scale=std, layers=L),
                     "wo": Leaf((h, hd, d), scale=std, layers=L),
                     "wq": Leaf((d, h, hd), scale=std, layers=L),
                     "wv": Leaf((d, kv, hd), scale=std, layers=L)},
            "ln1": {"scale": Leaf((d,), "ones", layers=L)},
            "ln2": {"scale": Leaf((d,), "ones", layers=L)},
            "mlp": {"wi_gate": Leaf((d, f), scale=std, layers=L),
                    "wi_up": Leaf((d, f), scale=std, layers=L),
                    "wo": Leaf((f, d), scale=std, layers=L)},
        },
        "ln_f": {"scale": Leaf((d,), "ones")},
    }


def program_fields(c: Dict) -> Dict:
    """The program's ``ArchConfig`` fields this configuration fixes."""
    return {"num_layers": c["num_hidden_layers"], "d_model": c["hidden_size"],
            "num_heads": c["num_attention_heads"],
            "num_kv_heads": c["num_key_value_heads"],
            "head_dim": c["head_dim"], "d_ff": c["intermediate_size"],
            "vocab_size": c["vocab_size"], "rope_theta": c["rope_theta"],
            "norm_eps": c["rms_norm_eps"],
            "tie_embeddings": c["tie_word_embeddings"],
            "attention": "full", "dtype": c["dtype"]["compute"]}


def _block(c: Dict, x, w, positions, quant: Optional[str]):
    n, S, d = x.shape
    h, kv, hd = c["num_attention_heads"], c["num_key_value_heads"], \
        c["head_dim"]
    eps = c["rms_norm_eps"]

    def mm(t, m):
        return ops.matmul(t.reshape(-1, t.shape[-1]), m, quant).reshape(
            *t.shape[:-1], m.shape[-1])

    a = ops.rmsnorm(x, w["ln1"], eps)
    q = mm(a, w["wq"].reshape(d, h * hd)).reshape(n, S, h, hd)
    k = mm(a, w["wk"].reshape(d, kv * hd)).reshape(n, S, kv, hd)
    v = mm(a, w["wv"].reshape(d, kv * hd)).reshape(n, S, kv, hd)
    q = ops.rope(q, positions, c["rope_theta"])
    k = ops.rope(k, positions, c["rope_theta"])
    group = h // kv
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    s = jnp.einsum("nqhd,nkhd->nhqk", q, k, precision=ops.HIGHEST) \
        / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("nhqk,nkhd->nqhd", p, v, precision=ops.HIGHEST)
    x = x + mm(o.reshape(n, S, h * hd), w["wo"].reshape(h * hd, d))
    a = ops.rmsnorm(x, w["ln2"], eps)
    g = mm(a, w["wi_gate"])
    u = mm(a, w["wi_up"])
    return x + mm(jax.nn.silu(g) * u, w["wo_mlp"])


def _logits(c: Dict, key, tokens, weight_dtype, quant: Optional[str]):
    """Logits over the true vocabulary at every position, the weights made
    layer by layer from ``key`` (``weights.base_key(seed)``)."""
    lay = layout(c)
    keys = W.leaf_keys(lay, key)
    L = c["num_hidden_layers"]

    def full(path):
        leaf, k = lay, keys
        for p in path:
            leaf, k = leaf[p], k[p]
        return W.leaf_value(leaf, k, weight_dtype).astype(jnp.float32)

    def layer(path, l):
        leaf, k = lay, keys
        for p in path:
            leaf, k = leaf[p], k[p]
        return W.layer_value(leaf, k, l, weight_dtype).astype(jnp.float32)

    table = full(("embed", "embedding"))
    x = table[tokens]
    positions = jnp.arange(tokens.shape[1])

    def body(x, l):
        w = {"ln1": layer(("layers", "ln1", "scale"), l),
             "ln2": layer(("layers", "ln2", "scale"), l),
             "wq": layer(("layers", "attn", "wq"), l),
             "wk": layer(("layers", "attn", "wk"), l),
             "wv": layer(("layers", "attn", "wv"), l),
             "wo": layer(("layers", "attn", "wo"), l),
             "wi_gate": layer(("layers", "mlp", "wi_gate"), l),
             "wi_up": layer(("layers", "mlp", "wi_up"), l),
             "wo_mlp": layer(("layers", "mlp", "wo"), l)}
        return _block(c, x, w, positions, quant), None

    x, _ = jax.lax.scan(body, x, jnp.arange(L))
    x = ops.rmsnorm(x, full(("ln_f", "scale")), c["rms_norm_eps"])
    V = c["vocab_size"]
    n, S, d = x.shape
    return ops.matmul(x.reshape(n * S, d), table[:V].T, quant).reshape(
        n, S, V)


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
    ref = _logits(c, key, tokens, weight_dtype, None)
    T = served.shape[1]
    ref = ref[:, first:first + T]
    if control:
        ctl = _logits(c, key, tokens, weight_dtype, "int8")
        served = jnp.argmax(ctl[:, first:first + T], axis=-1)
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, served[..., None], axis=-1)[..., 0]
    return best - got
