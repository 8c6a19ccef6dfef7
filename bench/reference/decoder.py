"""Plain reference of dense global-attention decoders: Qwen3 and
Phi-3(-vision), the configuration files whose ``reference`` is ``decoder``.

It exports the model reference's contract (``bench/reference/__init__.py``).
Straightforward ``jax.numpy`` in float32 at the highest matmul precision,
written from the published model descriptions (Hugging Face ``modeling_qwen3``
and ``modeling_phi3``), with no kernels, cache or batching tricks.  It imports
nothing of the program under test.

Weights are made by ``make_weights`` from the run's seed, in the layout below,
and handed both to this reference and to the program:

    embed (V, d)              token embedding (also the head when tied)
    head (V, d)               output head, untied models only
    final_norm (d,)
    layers_0: stacked over L
      ln1, ln2 (L, d)
      attn: wq (L, d, H*hd), wk, wv (L, d, KV*hd), wo (L, H*hd, d),
            q_norm, k_norm (L, hd) where the model has q/k norms
      mlp:  wi (L, d, 2*ff) = [gate | up], wo (L, ff, d)

RMSNorm weights are stored as offsets: the scale applied is ``1 + w``.

``precision="fp8"`` is the control: every linear layer's inputs and weights
are rounded to float8 e4m3 with a per-tensor absmax scale before the matmul,
the precision step below the bfloat16 the configurations state.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def shapes(model: dict) -> dict:
    """Leaf shapes of the weight layout for a configuration's ``model``."""
    d, ff = model["hidden_size"], model["intermediate_size"]
    h, kv, hd = (model["num_attention_heads"], model["num_key_value_heads"],
                 model["head_dim"])
    L, V = model["num_hidden_layers"], model["vocab_size"]
    attn = {"wq": (L, d, h * hd), "wk": (L, d, kv * hd), "wv": (L, d, kv * hd),
            "wo": (L, h * hd, d)}
    if model.get("qk_norm"):
        attn["q_norm"] = (L, hd)
        attn["k_norm"] = (L, hd)
    out = {"embed": (V, d), "final_norm": (d,),
           "layers_0": {"ln1": (L, d), "ln2": (L, d), "attn": attn,
                        "mlp": {"wi": (L, d, 2 * ff), "wo": (L, ff, d)}}}
    if not model.get("tie_word_embeddings"):
        out["head"] = (V, d)
    return out


def program_fields(m: dict):
    """-> (set, expect): the program's ``ArchConfig`` fields to set from the
    configuration's ``model`` block, and those it must already hold: a
    dense decoder with global attention, silu MLP, no experts, no softcaps."""
    set_ = {"n_layers": m["num_hidden_layers"], "norm_eps": m["rms_norm_eps"],
            "rope_theta": float(m["rope_theta"]), "dtype": m["torch_dtype"]}
    expect = {"d_model": m["hidden_size"], "d_ff": m["intermediate_size"],
              "n_heads": m["num_attention_heads"],
              "n_kv_heads": m["num_key_value_heads"],
              "resolved_head_dim": m["head_dim"], "vocab_size": m["vocab_size"],
              "tie_embeddings": m["tie_word_embeddings"],
              "qk_norm": m["qk_norm"], "qkv_bias": m.get("attention_bias", False),
              "n_frontend_tokens": m["frontend_tokens"], "mlp_act": "silu",
              "layer_pattern": ("global",), "n_experts": 0,
              "attn_logit_softcap": None, "final_logit_softcap": None,
              "sliding_window": None, "scale_embeddings": False,
              "use_post_norms": False}
    return set_, expect


def tiny(model: dict):
    """-> (model, fields): ``model`` at the CPU tests' size, 2 layers of
    hidden 64, 4 heads of 16, at most 2 kv heads, MLP 128, vocabulary 256
    and 8 image tokens where it takes images, in float32; and the program's
    ``ArchConfig`` fields that make the program that model."""
    kv = model["num_key_value_heads"]
    m = dict(model, num_hidden_layers=2, hidden_size=64, intermediate_size=128,
             num_attention_heads=4, num_key_value_heads=min(kv, 2),
             head_dim=16, vocab_size=256, torch_dtype="float32",
             frontend_tokens=8 if model["frontend_tokens"] else 0)
    set_, expect = program_fields(m)
    widths = ("d_model", "d_ff", "n_heads", "n_kv_heads", "vocab_size",
              "n_frontend_tokens")
    return m, {**set_, **{k: expect[k] for k in widths},
               "head_dim": m["head_dim"]}


def prefill_flops(model: dict, seq: int) -> float:
    """FLOPs of one prompt's prefill through ``model`` (a configuration
    file's ``model`` block) at ``seq`` positions, including image tokens."""
    d = model["hidden_size"]
    h = model["num_attention_heads"]
    kv = model["num_key_value_heads"]
    hd = model["head_dim"]
    ff = model["intermediate_size"]
    layers = model["num_hidden_layers"]
    proj = 2 * seq * d * (h + 2 * kv) * hd + 2 * seq * h * hd * d
    pairs = seq * (seq + 1) // 2                     # causal (q, k) pairs
    attn = 2 * 2 * pairs * h * hd                    # scores and weighted sum
    mlp = 2 * seq * d * 2 * ff + 2 * seq * ff * d    # gate+up, down
    head = 2 * d * model["vocab_size"]               # last position's logits
    return float(layers * (proj + attn + mlp) + head)


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def _scale(path: str, shape: tuple) -> float:
    """Standard deviation of a leaf: 1/sqrt(fan_in) for projections,
    1/sqrt(d) for embeddings and head, 0.1 for norm offsets."""
    leaf = path.rsplit("/", 1)[-1]
    if "norm" in leaf or leaf in ("ln1", "ln2"):
        return 0.1
    return float(1.0 / np.sqrt(shape[-1] if leaf in ("embed", "head")
                               else shape[-2]))


def make_weights(model: dict, key) -> dict:
    """All weights from one key, in one jitted call on the default device."""
    tree = shapes(model)
    leaves, treedef = jax.tree.flatten_with_path(tree, is_leaf=_is_shape)
    specs = [("/".join(str(getattr(k, "key", k)) for k in p), s)
             for p, s in leaves]

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(specs))
        return [jax.random.normal(k, s, jnp.float32) * _scale(p, s)
                for k, (p, s) in zip(keys, specs)]

    return jax.tree.unflatten(treedef, make(key))


# ------------------------------------------------------------------ forward
def _quant(x):
    """Round to float8 e4m3 with a per-tensor absmax scale (the control)."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def _linear(x, w, fp8: bool):
    if fp8:
        return _quant(x) @ _quant(w)
    return x @ w


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def _rope(x, theta):
    """Rotate-half RoPE over positions 0..S-1; x: (B, S, H, hd)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(model: dict, fp8: bool):
    h, kv, hd = (model["num_attention_heads"], model["num_key_value_heads"],
                 model["head_dim"])
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    ff = model["intermediate_size"]

    def body(x, p):
        B, S, _ = x.shape
        a = _rms(x, p["ln1"], eps)
        q = _linear(a, p["attn"]["wq"], fp8).reshape(B, S, h, hd)
        k = _linear(a, p["attn"]["wk"], fp8).reshape(B, S, kv, hd)
        v = _linear(a, p["attn"]["wv"], fp8).reshape(B, S, kv, hd)
        if "q_norm" in p["attn"]:
            q = _rms(q, p["attn"]["q_norm"], eps)
            k = _rms(k, p["attn"]["k_norm"], eps)
        q, k = _rope(q, theta), _rope(k, theta)
        rep = h // kv                      # query head i reads kv head i // rep
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
        causal = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(causal, s, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
        x = x + _linear(o.reshape(B, S, h * hd), p["attn"]["wo"], fp8)
        m = _linear(_rms(x, p["ln2"], eps), p["mlp"]["wi"], fp8)
        gate, up = m[..., :ff], m[..., ff:]
        return x + _linear(jax.nn.silu(gate) * up, p["mlp"]["wo"], fp8), None

    return body


def last_logits(model: dict, precision: str = "f32"):
    """jitted (weights, tokens (B, S), images (B, F, d) or None) -> (B, V)
    float32 logits of the last position."""
    fp8 = precision == "fp8"
    body = _layer(model, fp8)

    @jax.jit
    def fwd(w, tokens, images):
        with jax.default_matmul_precision("highest"):
            x = w["embed"][tokens]
            if images is not None:
                x = jnp.concatenate([images.astype(jnp.float32), x], axis=1)
            x, _ = jax.lax.scan(body, x, w["layers_0"])
            x = _rms(x[:, -1], w["final_norm"], model["rms_norm_eps"])
            head = w["embed"] if model.get("tie_word_embeddings") else w["head"]
            return _linear(x, head.T, fp8)

    return fwd
