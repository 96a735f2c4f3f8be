"""The plain reference: a decoder's published forward pass in float32.

What every decoder shares lives here: the embedding lookup, the final
RMSNorm and the logits against the output table (the embedding when tied),
and the blocks of arithmetic that model types build their layers from
(matmuls at ``Precision.HIGHEST``, so float32 is float32 on the TPU too;
RMSNorm; rotate-half RoPE; causal grouped-query attention).  Each layer is
the model type's own (``bench/models/<model_type>.py``: ``layer_weights``
and ``layer``), written from the model's description, not from the program.

The reference runs layer-outer on one device: each layer's weights are
gathered there once, every sequence of the sample goes through that layer
(each at its own padded length, by the same compiled layer as alone), and
the float32 hidden states stay on that device between layers.  Weights are
widened to float32 only inside the layer.  The weights are the benchmark's
own arrays (``harness.weights``), read by their roles in the layout the
program declares; nothing the program computed is read.

``control=True`` is the step down a later change might take: every weight
matrix and table rounded to int8 with one scale per output channel, and
every matmul fed bfloat16 (float32 accumulation).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

HIGHEST = jax.lax.Precision.HIGHEST
PAD = 1024          # sequences are padded to a multiple (causal: no effect)
QBLOCK = 256        # query rows per attention block


def int8_round(w, axis):
    """Symmetric int8 rounding with one scale per slice along ``axis``
    kept (the contracted axes share a scale)."""
    red = tuple(i for i in range(w.ndim) if i not in axis)
    scale = jnp.max(jnp.abs(w), axis=red, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(w / scale).clip(-127, 127) * scale


def mm(spec, a, b, control):
    if control:
        return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """Rotate-half RoPE: x [S, h, Dh], pos [S]."""
    Dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    x1, x2 = x[..., :Dh // 2], x[..., Dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def causal_gqa(q, k, v, pos, control):
    """Causal attention with scale Dh^-1/2 over q [S, KV, G, Dh] and k, v
    [S, KV, Dh] at positions ``pos`` [S] (``arange(S)``), QBLOCK query rows
    at a time; returns [S, KV * G, Dh]."""
    S, KV, G, Dh = q.shape

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * QBLOCK, QBLOCK, 0)
        qpos = i * QBLOCK + jnp.arange(QBLOCK)
        s = mm("qkgd,tkd->kgqt", qb, k, control) * Dh ** -0.5
        s = jnp.where(pos[None, None, None, :] <= qpos[None, None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return mm("kgqt,tkd->qkgd", p, v, control)

    o = jax.lax.map(block, jnp.arange(S // QBLOCK))
    return o.reshape(S, KV * G, Dh)


@functools.partial(jax.jit, static_argnames=("vocab", "eps", "control"))
def _head(x, norm, table, *, vocab, eps, control):
    """Logits [n, vocab] of the rows x [n, D]."""
    t = table[:vocab].astype(jnp.float32)
    if control:
        t = int8_round(t, (0,))
    xf = rms(x, norm.astype(jnp.float32), eps)
    return mm("nd,vd->nv", xf, t, control)


@functools.lru_cache(maxsize=None)
def _gather_fn(mesh):
    return jax.jit(lambda t: t, out_shardings=NamedSharding(
        mesh, PartitionSpec()))


def gather(tree, dev):
    """Every array of ``tree`` whole on ``dev``: an array sharded over a
    mesh is all-gathered over the chips' own links, then the copy on
    ``dev`` is kept; any other array is put there."""
    def one(a):
        sh = a.sharding
        if isinstance(sh, NamedSharding) and len(sh.device_set) > 1:
            whole = _gather_fn(sh.mesh)(a)
            return next(s.data for s in whole.addressable_shards
                        if s.device == dev)
        return jax.device_put(a, dev)
    return jax.tree.map(one, tree)


class Reference:
    def __init__(self, params, config: dict, model, device=None):
        self.params = params
        self.config = config
        self.model = model
        self.eps = float(config["rms_norm_eps"])
        self.vocab = int(config["vocab_size"])
        self.layers = int(config["num_hidden_layers"])
        self.dev = device if device is not None else jax.devices()[0]

    def _put(self, a):
        return jax.device_put(a, self.dev)

    def hidden(self, seqs: list, sides=(False,)) -> dict:
        """Final-layer hidden states [S_padded, D] (before the final norm)
        of each token sequence of ``seqs``, under each of ``sides`` (False:
        the reference; True: the control), layer by layer."""
        ids = []
        for tokens in seqs:
            S = len(tokens)
            padded = np.zeros(-(-S // PAD) * PAD, np.int32)
            padded[:S] = tokens
            ids.append(self._put(jnp.asarray(padded)))
        table = gather(self.params["embed"], self.dev)
        x = {control: [] for control in sides}
        for i in ids:
            rows = jnp.take(table, i, axis=0).astype(jnp.float32)
            for control in sides:
                # a row's int8 scale is its own: rounding the rows taken
                # is rounding the table, without a float32 copy of it
                x[control].append(int8_round(rows, (0,)) if control
                                  else rows)
        del table
        layer = {c: self.model.layer(self.config, c) for c in sides}
        for l in range(self.layers):
            w = gather(self.model.layer_weights(self.params, l), self.dev)
            for control in sides:
                x[control] = [layer[control](xi, w) for xi in x[control]]
            del w
        return x

    def head(self) -> tuple:
        """The final norm and the output table, on the reference device."""
        out = self.params.get("unembed", self.params["embed"])
        return gather((self.params["final_norm"], out), self.dev)

    def logits(self, x, rows: np.ndarray, head: tuple, control=False):
        """Float32 logits [len(rows), vocab] at positions ``rows``."""
        norm, out = head
        return _head(x[self._put(jnp.asarray(rows))], norm, out,
                     vocab=self.vocab, eps=self.eps, control=control)
