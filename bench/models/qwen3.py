"""Qwen3 (``model_type`` "qwen3"): the published dense decoder.

Written from the model's description (Qwen3 technical report; the Hugging
Face ``Qwen3ForCausalLM``), not from the program: per layer RMSNorm ->
q/k/v projections -> RMSNorm over each head's q and k -> rotary embedding
(rotate-half, base ``rope_theta``) -> causal grouped-query attention with
scale head_dim^-1/2 -> output projection -> residual; RMSNorm -> SwiGLU
(silu(x Wg) * (x Wu)) Wd -> residual.  Embedding, final norm and logits
are the harness's (``harness.reference``).

What the harness asks of a model type (``harness.spec.load_model``):
``model_config``, ``leaf_law``, ``layer_weights``, ``layer`` and ``cost``.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from harness.reference import causal_gqa, int8_round, mm, rms, rope


def model_config(config: dict):
    """The program's ``ModelConfig`` for the published sizes: GQA with
    per-head RMSNorm on q and k, RoPE, SwiGLU, RMSNorm before each
    sub-layer."""
    from repro.models.common import LayerGroup, ModelConfig
    if config.get("hidden_act") != "silu" or config.get("attention_bias"):
        raise ValueError("qwen3 mapping expects silu and no attention bias")
    L = int(config["num_hidden_layers"])
    return ModelConfig(
        name=config["name"], family="dense", num_layers=L,
        d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], d_ff=config["intermediate_size"],
        vocab_size=config["vocab_size"],
        groups=(LayerGroup(("attn",), L),), mlp_act="silu",
        rope_theta=float(config["rope_theta"]), qk_norm=True,
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        attn_mode="heads")


def leaf_law(name: str, shape: tuple) -> tuple[str, float]:
    """(kind, std) of a layer matrix: N(0, 1) / sqrt(fan_in), fan_in the
    width the matrix contracts.  Norms and tables are the harness's."""
    last = name.rsplit("/", 1)[-1]
    if last in ("wq", "wk", "wv", "wi_gate", "wi_up"):
        return "normal", shape[-3 if last in ("wq", "wk", "wv") else -2] ** -0.5
    if last == "wo" and len(shape) == 4:           # attention out [L,H,Dh,D]
        return "normal", (shape[-3] * shape[-2]) ** -0.5
    if last == "wo":                               # ffn down [L,F,D]
        return "normal", shape[-2] ** -0.5
    raise KeyError(f"no weight law for leaf {name!r} {shape}")


def layer_weights(params, l: int) -> dict:
    """Layer ``l``'s arrays by role, sliced from the program's stacked
    layout (``groups[0].sub0``: ``norm1``, ``attn``, ``norm2``, ``ffn``)."""
    g = params["groups"][0]["sub0"]
    a, f = g["attn"], g["ffn"]
    return {k: v[l] for k, v in (
        ("n1", g["norm1"]), ("n2", g["norm2"]), ("wq", a["wq"]),
        ("wk", a["wk"]), ("wv", a["wv"]), ("wo", a["wo"]),
        ("qn", a["q_norm"]), ("kn", a["k_norm"]), ("wg", f["wi_gate"]),
        ("wu", f["wi_up"]), ("wd", f["wo"]))}


@functools.partial(jax.jit, static_argnames=("eps", "theta", "control"))
def _layer(x, w, *, eps, theta, control):
    """One decoder layer over x [S, D] (float32)."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    if control:
        for k, ax in (("wq", (1, 2)), ("wk", (1, 2)), ("wv", (1, 2)),
                      ("wo", (2,)), ("wg", (1,)), ("wu", (1,)), ("wd", (1,))):
            w[k] = int8_round(w[k], ax)
    S = x.shape[0]
    KV, Dh = w["wk"].shape[1], w["wk"].shape[2]
    H = w["wq"].shape[1]
    G = H // KV
    pos = jnp.arange(S)
    h = rms(x, w["n1"], eps)
    q = rms(mm("sd,dhk->shk", h, w["wq"], control), w["qn"], eps)
    k = rms(mm("sd,dhk->shk", h, w["wk"], control), w["kn"], eps)
    v = mm("sd,dhk->shk", h, w["wv"], control)
    q = rope(q, pos, theta).reshape(S, KV, G, Dh)
    k = rope(k, pos, theta)
    o = causal_gqa(q, k, v, pos, control)
    x = x + mm("shk,hkd->sd", o, w["wo"], control)
    h = rms(x, w["n2"], eps)
    f = jax.nn.silu(mm("sd,df->sf", h, w["wg"], control)) * mm(
        "sd,df->sf", h, w["wu"], control)
    return x + mm("sf,fd->sd", f, w["wd"], control)


def layer(config: dict, control: bool):
    """``f(x, w)``: one float32 layer over x [S, D] with the weights of
    ``layer_weights``; ``control`` rounds the matrices to int8 and feeds
    the matmuls bfloat16."""
    return functools.partial(_layer, eps=float(config["rms_norm_eps"]),
                             theta=float(config["rope_theta"]),
                             control=control)


@dataclass(frozen=True)
class ModelCost:
    """Operations of a dense decoder with GQA attention and a SwiGLU FFN."""

    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int

    @classmethod
    def of(cls, config: dict) -> "ModelCost":
        return cls(config["num_hidden_layers"], config["hidden_size"],
                   config["num_attention_heads"],
                   config["num_key_value_heads"], config["head_dim"],
                   config["intermediate_size"], config["vocab_size"])

    @property
    def layer_params(self) -> int:
        D, H, KV, Dh, F = (self.d_model, self.heads, self.kv_heads,
                           self.head_dim, self.d_ff)
        return D * H * Dh + 2 * D * KV * Dh + H * Dh * D + 3 * D * F

    @property
    def matmul_params(self) -> int:
        """Parameters every token multiplies: all layers, not the embedding
        lookup; the output head counts per logit row."""
        return self.layers * self.layer_params

    def prefill_flops(self, prompt: int) -> float:
        """One prompt: every layer over every token, causal attention over
        P (P + 1) / 2 query-key pairs, and one row of logits."""
        P = prompt
        attn = 2.0 * self.layers * self.heads * self.head_dim * P * (P + 1)
        return (2.0 * self.matmul_params * P + attn
                + 2.0 * self.d_model * self.vocab)

    def decode_flops(self, context: int) -> float:
        """One generated token attending to ``context`` cache entries."""
        attn = 4.0 * self.layers * self.heads * self.head_dim * context
        return 2.0 * self.matmul_params + attn + 2.0 * self.d_model * self.vocab


def cost(config: dict) -> ModelCost:
    return ModelCost.of(config)
