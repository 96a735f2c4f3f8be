"""Per-architecture smoke tests (the brief's required reduced-config
checks): one forward/train step on CPU, asserting output shapes + no NaNs,
plus prefill->decode consistency for every family."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config, list_archs
from repro.models import blocks, encdec, lm
from repro.models.registry import (model_decode_step, model_loss,
                                   model_prefill, model_specs)
from repro.models.common import count_params, init_params
from repro.models.sharding import activation_sharding
from repro.serve import kvcache
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update

ARCHS = list_archs()


def _smoke_batch(cfg, B=2, S=16, key=0):
    k = jax.random.PRNGKey(key)
    toks = jax.random.randint(k, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}
    if cfg.encoder:
        batch["audio_embeds"] = jax.random.normal(
            jax.random.fold_in(k, 1), (B, 16, cfg.d_model), jnp.float32)
    if cfg.frontend:
        batch["extra_embeds"] = jax.random.normal(
            jax.random.fold_in(k, 2), (B, 4, cfg.d_model), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_matches_brief(arch):
    """The full config instantiates with the published dimensions."""
    cfg = get_config(arch)
    assert cfg.num_layers >= 1 and cfg.d_model >= 256
    assert cfg.num_heads % cfg.num_kv_heads == 0
    specs = model_specs(cfg)
    n = count_params(specs)
    floor = 3e7 if arch in ("whisper-tiny", "xlstm-125m") else 1e9
    assert n > floor, f"{arch}: {n} params looks too small"


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_and_loss(arch):
    cfg = get_smoke_config(arch)
    specs = model_specs(cfg)
    params = init_params(specs, jax.random.PRNGKey(0))
    batch = _smoke_batch(cfg)
    loss, metrics = jax.jit(lambda p, b: model_loss(p, b, cfg))(params, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), f"{arch}: non-finite loss"
    assert float(loss) > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step(arch):
    """One full fwd+bwd+AdamW update: params change, stay finite."""
    cfg = get_smoke_config(arch)
    specs = model_specs(cfg)
    params = init_params(specs, jax.random.PRNGKey(0))
    opt = adamw_init(params)
    batch = _smoke_batch(cfg)

    def step(p, o, b):
        loss, grads = jax.value_and_grad(
            lambda pp: model_loss(pp, b, cfg)[0])(p)
        p2, o2, m = adamw_update(grads, o, p, 1e-3, cfg=AdamWConfig())
        return p2, o2, loss

    p2, o2, loss = jax.jit(step)(params, opt, batch)
    assert bool(jnp.isfinite(loss))
    # at least one leaf moved
    moved = any(
        float(jnp.max(jnp.abs(a.astype(jnp.float32)
                              - b.astype(jnp.float32)))) > 0
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)))
    assert moved
    finite = all(bool(jnp.all(jnp.isfinite(l.astype(jnp.float32))))
                 for l in jax.tree.leaves(p2))
    assert finite


DECODE_TOL = {            # MoE capacity dropping is batch-context dependent
    "mixtral-8x7b": 3.0, "qwen3-moe-30b-a3b": 3.5, "jamba-v0.1-52b": 3.0,
    "xlstm-125m": 0.2,    # bf16 conv accumulation-order noise
}


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_consistency(arch):
    """Decode of token S against prefill caches == full forward at pos S."""
    cfg = get_smoke_config(arch)
    specs = model_specs(cfg)
    params = init_params(specs, jax.random.PRNGKey(0))
    B, S, F = 2, 8, 4
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S + 1), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks[:, :S]}
    if cfg.encoder:
        batch["audio_embeds"] = jax.random.normal(
            jax.random.PRNGKey(2), (B, 16, cfg.d_model), jnp.float32)
    elif cfg.frontend:
        batch["extra_embeds"] = jax.random.normal(
            jax.random.PRNGKey(3), (B, F, cfg.d_model), jnp.float32)
    off = F if (cfg.frontend and not cfg.encoder) else 0
    cap = S + off + 4
    _, caches = model_prefill(params, batch, cfg, capacity=cap)
    logits_dec, _ = model_decode_step(
        params, toks[:, S:S + 1], caches, cfg,
        pos=jnp.full((B,), S + off, jnp.int32))
    ref_batch = dict(batch, tokens=toks)
    logits_ref, _ = model_prefill(params, ref_batch, cfg, capacity=cap)
    err = float(jnp.max(jnp.abs(
        logits_dec[:, 0].astype(jnp.float32)
        - logits_ref[:, -1].astype(jnp.float32))))
    tol = DECODE_TOL.get(arch, 1e-3)
    assert err <= tol, f"{arch}: decode err {err} > {tol}"


def _per_layer_decode(x, group_params, caches, cfg, *, pos, write_idx,
                      paged=None):
    """Reference for ``blocks.run_groups_decode``: a plain Python loop over
    the layers, no scan.  Each layer decodes against its own slice of every
    cache leaf (its self-attention K/V/pos as a one-layer stack), and the
    slice is written back whole.  Optimisation barriers bound each layer as
    the scan body does, so XLA fuses (and rounds) within a layer alike."""
    assert paged is None
    new_caches = []
    for group, gp, gc in zip(cfg.groups, group_params, caches):
        for i in range(group.repeats):
            layer_p = jax.tree.map(lambda a: a[i], gp)
            own = {}
            for j, kind in enumerate(group.pattern):
                s, attn = f"sub{j}", kind.startswith("attn")
                own[s] = {k: a[i:i + 1] if attn and k in ("k", "v", "pos")
                          else a[i] for k, a in gc[s].items()}
            x, own = jax.lax.optimization_barrier((x, own))
            for j, kind in enumerate(group.pattern):
                s = f"sub{j}"
                x, own[s] = blocks.block_decode(
                    kind, x, layer_p[s], cfg, own[s], pos=pos,
                    write_idx=write_idx, layer=0)
            x, own = jax.lax.optimization_barrier((x, own))
            gc = {s: {k: gc[s][k].at[i].set(     # [0]: a one-layer stack
                          a[0] if a.ndim == gc[s][k].ndim else a)
                      for k, a in own[s].items()}
                  for s in gc}
        new_caches.append(gc)
    return x, new_caches


@pytest.mark.parametrize("impl", ["ref", "pallas"])
@pytest.mark.parametrize("arch", ["qwen3-4b", "mixtral-8x7b", "jamba-v0.1-52b",
                                  "whisper-tiny", "xlstm-125m"])
def test_decode_scan_matches_per_layer_loop(arch, impl, monkeypatch):
    """The decode scan, which carries the stacked self-attention caches and
    writes each new row in place, against a per-layer loop: 12 greedy steps
    from three slots at different positions (the SWA ring wraps for
    mixtral), bitwise equal tokens and caches.  Dense, ring, hybrid (mamba
    states scanned), enc-dec (cross caches scanned) and xLSTM (all scanned);
    ``pallas`` is the flash-decode kernel in interpret mode."""
    cfg = get_smoke_config(arch)
    params = init_params(model_specs(cfg), jax.random.PRNGKey(0))
    B, S = 3, 10
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks}
    if cfg.encoder:
        batch["audio_embeds"] = jax.random.normal(
            jax.random.PRNGKey(2), (B, 16, cfg.d_model), jnp.float32)
    _, caches = model_prefill(params, batch, cfg, capacity=24)
    lengths = jnp.array([3, 6, 10], jnp.int32)
    caches = kvcache.mask_prefill_pos(cfg, caches, lengths)

    def run():
        step = jax.jit(lambda p, t, c, q: model_decode_step(p, t, c, cfg,
                                                            pos=q))
        c, pos = caches, lengths
        tok = toks[jnp.arange(B), lengths - 1][:, None]
        out = []
        with activation_sharding({"decode_attn_impl": impl}):
            for _ in range(12):
                logits, c = step(params, tok, c, pos)
                tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
                out.append(np.asarray(tok))
                pos = pos + 1
        return np.concatenate(out, axis=1), c

    tokens, got = run()
    for mod in (lm, encdec):
        monkeypatch.setattr(mod, "run_groups_decode", _per_layer_decode)
    ref_tokens, want = run()
    np.testing.assert_array_equal(tokens, ref_tokens)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a.astype(jnp.float32)),
                                      np.asarray(b.astype(jnp.float32)))
