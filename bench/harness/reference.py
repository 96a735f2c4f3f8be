"""The plain reference: Qwen3's published forward pass in float32.

Written from the model's description (Qwen3 technical report; the Hugging
Face ``Qwen3ForCausalLM``), not from the program: embedding lookup; per
layer RMSNorm -> q/k/v projections -> RMSNorm over each head's q and k ->
rotary embedding (rotate-half, base ``rope_theta``) -> causal grouped-query
attention with scale head_dim^-1/2 -> output projection -> residual;
RMSNorm -> SwiGLU (silu(x Wg) * (x Wu)) Wd -> residual; final RMSNorm ->
logits against the output table (the embedding when tied).  Every matmul
runs at ``Precision.HIGHEST``, so float32 is float32 on the TPU too.

It runs one layer at a time on one device, with each layer's weights
brought there and widened to float32 only while that layer runs, so it fits
beside the weights.  The weights are the benchmark's own arrays
(``harness.weights``), read by their roles in the layout the program
declares; nothing the program computed is read.

``control=True`` is the step down a later change might take: every weight
matrix and table rounded to int8 with one scale per output channel, and
every matmul fed bfloat16 (float32 accumulation).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
PAD = 1024          # sequences are padded to a multiple (causal: no effect)
QBLOCK = 256        # query rows per attention block


def _int8_round(w, axis):
    """Symmetric int8 rounding with one scale per slice along ``axis``
    kept (the contracted axes share a scale)."""
    red = tuple(i for i in range(w.ndim) if i not in axis)
    scale = jnp.max(jnp.abs(w), axis=red, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(w / scale).clip(-127, 127) * scale


def _mm(spec, a, b, control):
    if control:
        return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """Rotate-half RoPE: x [S, h, Dh], pos [S]."""
    Dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    x1, x2 = x[..., :Dh // 2], x[..., Dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


@functools.partial(jax.jit, static_argnames=("eps", "theta", "control"))
def _layer(x, w, *, eps, theta, control):
    """One decoder layer over x [S, D] (float32)."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    if control:
        for k, ax in (("wq", (1, 2)), ("wk", (1, 2)), ("wv", (1, 2)),
                      ("wo", (2,)), ("wg", (1,)), ("wu", (1,)), ("wd", (1,))):
            w[k] = _int8_round(w[k], ax)
    S = x.shape[0]
    KV, Dh = w["wk"].shape[1], w["wk"].shape[2]
    H = w["wq"].shape[1]
    G = H // KV
    pos = jnp.arange(S)
    h = _rms(x, w["n1"], eps)
    q = _rms(_mm("sd,dhk->shk", h, w["wq"], control), w["qn"], eps)
    k = _rms(_mm("sd,dhk->shk", h, w["wk"], control), w["kn"], eps)
    v = _mm("sd,dhk->shk", h, w["wv"], control)
    q = _rope(q, pos, theta).reshape(S, KV, G, Dh)
    k = _rope(k, pos, theta)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * QBLOCK, QBLOCK, 0)
        qpos = i * QBLOCK + jnp.arange(QBLOCK)
        s = _mm("qkgd,tkd->kgqt", qb, k, control) * Dh ** -0.5
        s = jnp.where(pos[None, None, None, :] <= qpos[None, None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _mm("kgqt,tkd->qkgd", p, v, control)

    o = jax.lax.map(block, jnp.arange(S // QBLOCK))
    o = o.reshape(S, H, Dh)
    x = x + _mm("shk,hkd->sd", o, w["wo"], control)
    h = _rms(x, w["n2"], eps)
    f = jax.nn.silu(_mm("sd,df->sf", h, w["wg"], control)) * _mm(
        "sd,df->sf", h, w["wu"], control)
    return x + _mm("sf,fd->sd", f, w["wd"], control)


@functools.partial(jax.jit, static_argnames=("vocab", "eps", "control"))
def _head(x, norm, table, *, vocab, eps, control):
    """Logits [n, vocab] of the rows x [n, D]."""
    t = table[:vocab].astype(jnp.float32)
    if control:
        t = _int8_round(t, (0,))
    xf = _rms(x, norm.astype(jnp.float32), eps)
    return _mm("nd,vd->nv", xf, t, control)


def _roles(params):
    g = params["groups"][0]["sub0"]
    return g, params["embed"], params.get("unembed", params["embed"])


class Reference:
    def __init__(self, params, config: dict, device=None, control=False):
        self.params = params
        self.eps = float(config["rms_norm_eps"])
        self.theta = float(config["rope_theta"])
        self.vocab = int(config["vocab_size"])
        self.layers = int(config["num_hidden_layers"])
        self.control = control
        self.dev = device if device is not None else jax.devices()[0]

    def _put(self, a):
        return jax.device_put(a, self.dev)

    def _layer_weights(self, g, l):
        a, f = g["attn"], g["ffn"]
        return {k: self._put(v[l]) for k, v in (
            ("n1", g["norm1"]), ("n2", g["norm2"]), ("wq", a["wq"]),
            ("wk", a["wk"]), ("wv", a["wv"]), ("wo", a["wo"]),
            ("qn", a["q_norm"]), ("kn", a["k_norm"]), ("wg", f["wi_gate"]),
            ("wu", f["wi_up"]), ("wd", f["wo"]))}

    def hidden(self, tokens: np.ndarray):
        """Final-layer hidden states [S_padded, D] (before the final norm)
        of the token sequence."""
        g, embed, _ = _roles(self.params)
        S = len(tokens)
        Sp = -(-S // PAD) * PAD
        ids = np.zeros(Sp, np.int32)
        ids[:S] = tokens
        table = self._put(embed)
        if self.control:
            table = _int8_round(table.astype(jnp.float32), (0,))
        x = jnp.take(table, self._put(jnp.asarray(ids)), axis=0).astype(
            jnp.float32)
        del table
        for l in range(self.layers):
            x = _layer(x, self._layer_weights(g, l), eps=self.eps,
                       theta=self.theta, control=self.control)
        return x

    def logits(self, x, rows: np.ndarray):
        """Float32 logits [len(rows), vocab] at positions ``rows``."""
        _, _, out = _roles(self.params)
        return _head(x[self._put(jnp.asarray(rows))],
                     self._put(self.params["final_norm"]), self._put(out),
                     vocab=self.vocab, eps=self.eps, control=self.control)
