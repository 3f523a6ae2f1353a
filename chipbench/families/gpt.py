"""The GPT block, for every `model_type` that names it (`gpt2.py`,
`gpt_neo.py`): pre-norm layers of layer norm, multi-head attention with
one head dim for q, k and v, and a tanh-GELU MLP, layer-stacked in one
group `dec`; learned positions and a tied LM head.  LoRA on q, k, v and
o, each d -> d."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.harness import BenchError
from chipbench.reference import attention, lora_linear

GROUP = "dec"
TARGETS = ("q", "k", "v", "o")


def make_dims(cfg, *, d, layers, heads, d_ff, positions, windows):
    """The family's dims from the sizes each model_type's keys give."""
    if cfg["activation_function"] != "gelu_new":
        raise BenchError("the GPT family implements gelu_new only")
    att = cfg["attention"]
    hd = d // heads
    scale = {"1/sqrt(head_dim)": hd ** -0.5, "none": 1.0}[att["scale"]]
    layer = [{"kind": "gpt", "group": GROUP, "index": l,
              "attn": {"heads": heads, "kv_heads": heads, "qk_dim": hd,
                       "v_dim": hd, "window": w, "scale": scale},
              "targets": {t: (d, d) for t in TARGETS},
              "macs": 4 * d * d + 2 * d * d_ff}
             for l, w in enumerate(windows)]
    return {"layers": layers, "layer": layer, "vocab": cfg["vocab_size"],
            "head_macs": d * cfg["vocab_size"], "d_model": d,
            "heads": heads, "d_ff": d_ff, "positions": positions,
            "eps": cfg["layer_norm_epsilon"], "qkv_bias": att["qkv_bias"],
            "out_bias": att["out_bias"]}


def program_sizes(arch):
    m = arch.model
    return {"d_model": m.d_model, "layers": m.num_layers,
            "heads": m.num_heads, "d_ff": m.d_ff, "vocab": m.vocab_size,
            "positions": m.max_position_embeddings, "eps": m.norm_eps}


def base_shapes(dims):
    d, L, ff = dims["d_model"], dims["layers"], dims["d_ff"]
    s = {"embed/tok": (dims["vocab"], d), "embed/pos": (dims["positions"], d),
         "final_norm/scale": (d,), "final_norm/bias": (d,),
         "dec/norm1/scale": (L, d), "dec/norm1/bias": (L, d),
         "dec/norm2/scale": (L, d), "dec/norm2/bias": (L, d),
         "dec/wq": (L, d, d), "dec/wk": (L, d, d), "dec/wv": (L, d, d),
         "dec/wo": (L, d, d), "dec/w_in": (L, d, ff), "dec/w_out": (L, ff, d),
         "dec/b_in": (L, ff), "dec/b_out": (L, d)}
    if dims["qkv_bias"]:
        s.update({"dec/bq": (L, d), "dec/bk": (L, d), "dec/bv": (L, d)})
    if dims["out_bias"]:
        s["dec/bo"] = (L, d)
    return s


def draw(key, path, shape, dtype):
    leaf = path.rsplit("/", 1)[-1]
    if leaf == "scale":                       # layer-norm gains
        return 1.0 + 0.1 * jax.random.normal(key, shape, dtype)
    if leaf in ("tok", "pos"):
        return 0.02 * jax.random.normal(key, shape, dtype)
    if leaf.startswith("b") or path.endswith("/bias"):
        return 0.02 * jax.random.normal(key, shape, dtype)
    fan_in = shape[-2]
    return jax.random.normal(key, shape, dtype) * fan_in ** -0.5


# ---------------------------------------------------------------------------
# the model half of the reference


def layer_norm(x, scale, bias, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
    y = (xf - mu) / jnp.sqrt(var + eps) * scale.astype(jnp.float32)
    return (y + bias.astype(jnp.float32)).astype(x.dtype)


def gelu_tanh(x):
    """GPT-2's gelu_new."""
    c = math.sqrt(2.0 / math.pi)
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x ** 3)))


def embed(params, tokens, dims):
    s = tokens.shape[-1]
    return params["embed"]["tok"][tokens] + params["embed"]["pos"][:s]


def _layer(params, l):
    """The weight tree (program layout, layer-stacked) at layer l."""
    dec = params[GROUP]
    p = {"ln1_s": dec["norm1"]["scale"][l], "ln1_b": dec["norm1"]["bias"][l],
         "ln2_s": dec["norm2"]["scale"][l], "ln2_b": dec["norm2"]["bias"][l],
         "wq": dec["wq"][l], "wk": dec["wk"][l], "wv": dec["wv"][l],
         "wo": dec["wo"][l], "w_in": dec["w_in"][l], "w_out": dec["w_out"][l],
         "b_in": dec["b_in"][l], "b_out": dec["b_out"][l]}
    for nm in ("bq", "bk", "bv", "bo"):
        if nm in dec:
            p[nm] = dec[nm][l]
    return p


def block(x, params, l, ads, dims):
    """One pre-norm GPT block at layer l; ads: target -> (A, B, scale)."""
    p, att = _layer(params, l), dims["layer"][l]["attn"]
    h, hd = att["heads"], att["qk_dim"]
    lead = x.shape[:-1]
    y = layer_norm(x, p["ln1_s"], p["ln1_b"], dims["eps"])
    q = lora_linear(y, p["wq"], p.get("bq"), ads.get("q"))
    k = lora_linear(y, p["wk"], p.get("bk"), ads.get("k"))
    v = lora_linear(y, p["wv"], p.get("bv"), ads.get("v"))
    split = lambda t: t.reshape(lead + (h, hd))         # noqa: E731
    o = attention(split(q), split(k), split(v), att["window"], att["scale"])
    x = x + lora_linear(o.reshape(lead + (h * hd,)), p["wo"], p.get("bo"),
                        ads.get("o"))
    y = layer_norm(x, p["ln2_s"], p["ln2_b"], dims["eps"])
    hmid = gelu_tanh(y @ p["w_in"] + p["b_in"])
    return x + hmid @ p["w_out"] + p["b_out"]


def head(params, x, dims):
    """Final norm and the tied head: float32 logits."""
    x = layer_norm(x, params["final_norm"]["scale"],
                   params["final_norm"]["bias"], dims["eps"])
    return (x @ params["embed"]["tok"].T).astype(jnp.float32)
