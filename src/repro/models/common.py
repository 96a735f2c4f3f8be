"""Model configuration and parameter-spec machinery.

A model is described by a ``ModelConfig``.  Parameters are declared once as a
pytree of ``PSpec`` (shape, dtype, logical axes, init law); that single tree is
used to

  * materialize params with a PRNG   (``init_params``)
  * build ``jax.ShapeDtypeStruct``s for the dry-run (``abstract_params``)
  * derive ``PartitionSpec``s from logical-axis rules (``partition_specs``)

so init, sharding and lowering can never drift apart.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

# ---------------------------------------------------------------------------
# Sub-configs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_ff_expert: int
    capacity_factor: float = 1.25
    router_jitter: float = 0.0
    # load-balancing aux loss weight (Switch/GShard style)
    aux_loss_weight: float = 0.01


@dataclass(frozen=True)
class SSMConfig:
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0          # 0 -> ceil(d_model / 16)
    chunk: int = 256          # chunked-scan block length


@dataclass(frozen=True)
class XLSTMConfig:
    # projection factors from the xLSTM paper
    mlstm_proj_factor: float = 2.0
    slstm_proj_factor: float = 4.0 / 3.0
    conv_window: int = 4
    chunk: int = 256


@dataclass(frozen=True)
class EncoderConfig:
    num_layers: int
    seq_len: int              # fixed frontend length (e.g. 1500 audio frames)
    d_model: int = 0          # 0 -> same as decoder d_model
    num_heads: int = 0        # 0 -> same as decoder


# ---------------------------------------------------------------------------
# Block pattern
# ---------------------------------------------------------------------------
# A model body is a list of homogeneous *groups*; each group is (pattern,
# repeats) and lowers to one lax.scan over params stacked along a leading
# "layers" axis of length `repeats`.  `pattern` is a tuple of block kinds, one
# entry per sub-layer of the scan body.
#
# Block kinds: "attn", "attn_moe", "mamba", "mamba_moe", "mlstm", "slstm".

BlockKind = str


@dataclass(frozen=True)
class LayerGroup:
    pattern: tuple[BlockKind, ...]
    repeats: int

    @property
    def num_layers(self) -> int:
        return len(self.pattern) * self.repeats


# ---------------------------------------------------------------------------
# ModelConfig
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | hybrid | ssm | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0              # 0 -> d_model // num_heads
    groups: tuple[LayerGroup, ...] = ()
    # attention
    rope_theta: float = 10000.0
    use_rope: bool = True
    pos_emb: str = "rope"          # rope | learned
    max_position_embeddings: int = 0
    scale_embeddings: bool = False  # gemma-style sqrt(d_model) embed scale
    qk_norm: bool = False
    sliding_window: Optional[int] = None
    attn_logit_softcap: Optional[float] = None
    attn_mode: str = "auto"        # auto | heads | sequence
    # mlp
    mlp_act: str = "silu"          # silu (SwiGLU) | gelu (GeGLU)
    # sub-modules
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    xlstm: Optional[XLSTMConfig] = None
    encoder: Optional[EncoderConfig] = None
    frontend: Optional[str] = None  # None | audio_stub | vision_stub
    frontend_len: int = 0           # number of frontend embedding positions
    # misc
    norm_eps: float = 1e-6
    tie_embeddings: bool = True
    logit_softcap: Optional[float] = None
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat_policy: str = "minimal"  # none | minimal | full
    # True when long_500k is feasible (sub-quadratic context handling)
    subquadratic: bool = False

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)
        if not self.groups:
            object.__setattr__(self, "groups", (LayerGroup(("attn",), self.num_layers),))
        n = sum(g.num_layers for g in self.groups)
        assert n == self.num_layers, f"groups cover {n} layers != num_layers {self.num_layers}"

    # convenience ----------------------------------------------------------
    @property
    def q_per_kv(self) -> int:
        return self.num_heads // self.num_kv_heads

    @property
    def padded_vocab(self) -> int:
        """Vocab rounded up to a multiple of 256 so the embedding /
        unembedding tables shard over any TP axis ≤ 256 (whisper's 51865,
        internvl2's 92553 and qwen3's 151936 are not 16-divisible).  Token
        ids never index the pad rows; lm_head masks the pad logits."""
        return -(-self.vocab_size // 256) * 256

    def scaled(self, **overrides) -> "ModelConfig":
        """Return a copy with overridden fields (used by smoke tests)."""
        return dataclasses.replace(self, **overrides)


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class PSpec:
    """Declarative parameter spec: shape + dtype + logical axes + init law."""

    shape: tuple[int, ...]
    axes: tuple[Optional[str], ...]
    init: str = "normal"           # normal | zeros | ones | scaled:<fan_in>
    dtype: Any = None              # None -> config.param_dtype

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _init_leaf(spec: PSpec, key: jax.Array, param_dtype) -> jax.Array:
    dtype = spec.dtype or param_dtype
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    if spec.init.startswith("scaled:"):
        fan_in = float(spec.init.split(":")[1])
        std = 1.0 / math.sqrt(max(fan_in, 1.0))
        return (jax.random.normal(key, spec.shape, jnp.float32) * std).astype(dtype)
    if spec.init == "normal":
        return (jax.random.normal(key, spec.shape, jnp.float32) * 0.02).astype(dtype)
    if spec.init == "arange_log":
        # S4/Mamba A-matrix init: A = -exp(A_log), A_log = log(1..N) per row
        n = spec.shape[-1]
        row = jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))
        return jnp.broadcast_to(row, spec.shape).astype(dtype)
    if spec.init.startswith("const:"):
        return jnp.full(spec.shape, float(spec.init.split(":")[1]), dtype)
    raise ValueError(f"unknown init {spec.init}")


def is_pspec(x) -> bool:
    return isinstance(x, PSpec)


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def _init_leaf_jit(spec: PSpec, key: jax.Array, param_dtype, sharding):
    x = _init_leaf(spec, key, param_dtype)
    return x if sharding is None else jax.lax.with_sharding_constraint(
        x, sharding)


def init_params(specs, key: jax.Array, param_dtype=jnp.float32,
                shardings=None):
    """Materialize a PSpec tree into arrays, folding the key per leaf path.

    Each leaf is one jitted program, cached on its spec: the RNG, scale and
    cast fuse, so no f32 copy of a whole bf16 weight is ever live (a
    4 B-parameter model then fits one 16 GB chip).  ``shardings`` (a
    matching tree of ``NamedSharding``) makes every leaf born in its
    sharding instead of landing whole on the first device."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=is_pspec)
    shards = (jax.tree.leaves(shardings) if shardings is not None
              else [None] * len(leaves))
    out = [_init_leaf_jit(leaf, jax.random.fold_in(key, i), param_dtype, sh)
           for i, (leaf, sh) in enumerate(zip(leaves, shards, strict=True))]
    return jax.tree.unflatten(treedef, out)


def abstract_params(specs, param_dtype=jnp.float32):
    """PSpec tree -> ShapeDtypeStruct tree (dry-run stand-ins; no allocation)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype or param_dtype),
        specs,
        is_leaf=is_pspec,
    )


def partition_specs(specs, rules: dict[Optional[str], Optional[str]]):
    """PSpec tree -> PartitionSpec tree via logical-axis rules.

    ``rules`` maps logical axis name -> mesh axis name (or None).  Logical
    axes missing from the rules are unsharded.  If two tensor dims map to the
    same mesh axis, the later dim is left unsharded (a mesh axis may shard at
    most one dim of a tensor).
    """

    def one(s: PSpec):
        used: set[str] = set()
        out = []
        for ax in s.axes:
            mesh_ax = rules.get(ax)
            if mesh_ax is None or mesh_ax in used:
                out.append(None)
            else:
                # mesh_ax may be a tuple of axes (e.g. ("pod","data"))
                key = mesh_ax if isinstance(mesh_ax, str) else tuple(mesh_ax)
                if isinstance(key, tuple):
                    if any(k in used for k in key):
                        out.append(None)
                        continue
                    used.update(key)
                else:
                    used.add(key)
                out.append(mesh_ax)
        return P(*out)

    return jax.tree.map(one, specs, is_leaf=is_pspec)


def count_params(specs) -> int:
    leaves = jax.tree.leaves(specs, is_leaf=is_pspec)
    return int(sum(math.prod(l.shape) for l in leaves))


# ---------------------------------------------------------------------------
# divisibility helpers used by sharding rule selection
# ---------------------------------------------------------------------------


def divides(a: int, b: int) -> bool:
    return b > 0 and a > 0 and a % b == 0
