"""One ``repro.runtime`` surface: fabric -> Plan -> specs/params -> executables.

The paper brings a tiered machine up through one disciplined sequence
(substrate -> links -> memory -> workload); ``Runtime`` is that sequence as
an object.  ``Runtime.create(arch, mesh, shape_kind=...)`` owns the whole
chain — arch registry lookup, fabric-aware ``Plan``, parameter specs, lazy
param materialization, and cached jitted executables — so every driver
(launchers, examples, benchmarks, the serve engine, the dry-run cells)
assembles the stack through one entry point instead of re-wiring
``make_plan`` + ``model_specs`` + step factories by hand.

    rt = Runtime.create("gemma-2b", "2x4", shape_kind="train", seq_len=512,
                        smoke=True)
    print(rt.describe())                  # plan + tiers + kernels, one report
    state = rt.init_train_state()
    state, metrics = rt.train_step(state, batch)

    srv = rt.reshape(shape_kind="decode", capacity=128)
    logits, caches = srv.prefill(batch)   # model-level executables
    logits, caches = srv.decode_step(token, caches, pos)
    engine = srv.engine(num_slots=8)      # continuous-batching serve engine
"""
from __future__ import annotations

import contextlib
import math
from typing import Any, Callable, Optional, Union

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.configs import get_config, get_smoke_config
from repro.core import topology
from repro.core.topology import Plan, batch_pspec, make_plan, mesh_axes_of
from repro.models import registry
from repro.models.common import (ModelConfig, count_params, init_params,
                                 is_pspec, partition_specs)
from repro.models.sharding import activation_sharding
from repro.serve import steps as serve_steps
from repro.train import steps as train_steps
from repro.train import state as train_state_mod


class Runtime:
    """Everything one (arch × mesh × shape) cell needs, in one object.

    Build with :meth:`create`; the constructor is internal plumbing.
    Model-level executables (``prefill`` / ``decode_step`` / ``loss``)
    return logits and are jitted once per Runtime; engine-level serve steps
    (greedy sampling, donated caches) come from :meth:`make_prefill_step` /
    :meth:`make_decode_step` and power :meth:`engine`.
    """

    def __init__(self, *, arch: str, cfg: ModelConfig,
                 family: registry.ModelFamily, mesh, plan: Plan, specs,
                 seq_len: int, capacity: int, attn_impl: str,
                 ffn_impl: str = "auto", kv_layout: str = "dense",
                 kv_dtype: str = "f32",
                 partition: str = "auto", scheduler: bool = False,
                 sched_kw=None,
                 param_dtype=jnp.float32, seed: int = 0, params=None,
                 plan_kw=None):
        self.arch = arch
        self.cfg = cfg
        self.family = family
        self.caps = family.capabilities(cfg)
        self.mesh = mesh
        self.plan = plan
        self.specs = specs
        self.seq_len = seq_len
        self.capacity = capacity
        self.attn_impl = attn_impl          # requested; resolution is lazy
        self.ffn_impl = ffn_impl            # requested; resolution is lazy
        self.kv_layout = kv_layout          # serve KV layout: dense | paged
        self.kv_dtype = kv_dtype            # paged pool storage: f32 | int8
        self.partition = partition          # shard_map kernel dispatch knob
        self.scheduler = scheduler          # chunked-prefill serve scheduler
        self.sched_kw = dict(sched_kw or {})  # token_budget/chunk_size/...
        self.param_dtype = param_dtype
        self.seed = seed
        self.plan_kw = dict(plan_kw or {})
        self._params = params
        self._exec: dict[str, Callable] = {}
        self._burn_in = None       # BurnInReport once burn_in() has run
        self._telemetry = None     # lazy obs.Telemetry (telemetry())
        self._link_monitor = None  # lazy linktest.LinkMonitor

    # -- construction -------------------------------------------------------

    @classmethod
    def create(cls, arch: Union[str, ModelConfig], mesh=None, *,
               shape_kind: str = "decode", smoke: bool = False,
               seq_len: Optional[int] = None, capacity: Optional[int] = None,
               grad_sync: str = "hierarchical", attn_impl: str = "auto",
               ffn_impl: str = "auto", kv_layout: str = "dense",
               kv_dtype: str = "f32",
               partition: str = "auto", scheduler: bool = False,
               sched_kw: Optional[dict] = None,
               param_dtype=jnp.float32, seed: int = 0, params=None,
               plan_kw: Optional[dict] = None) -> "Runtime":
        """Build the full chain for one cell.

        ``arch`` is a registry name from ``repro.configs.ARCHS`` (``smoke``
        selects the reduced same-family config) or a ready ``ModelConfig``.
        ``mesh`` is a ``jax.sharding.Mesh``, a spec string like ``"2x4"``
        (resolved via ``launch.mesh.mesh_from_spec``), or None for the
        single-device/unsharded plan.  ``seq_len`` sizes the plan's
        activation decisions; ``capacity`` is the decode-cache length used
        by prefill/decode executables and the serve engine (they default to
        each other, else 128).  ``kv_layout`` picks the serve-engine KV
        layout: "dense" per-slot slabs, or "paged" pooled block caches
        (arch-gated by ``caps.supports_paged_decode``; fails fast here).
        ``kv_dtype`` picks the paged pool's storage: "f32" full precision,
        or "int8" quantized blocks with per-(entry, kv-head) scales and
        in-kernel dequant decode (requires ``kv_layout="paged"`` and
        ``caps.supports_quantized_kv``; fails fast here).
        ``partition`` ("auto" | "off") controls the shard_map kernel
        dispatch (kernels.partition): "auto" runs each Pallas kernel on
        head-/column-/row-sharded operands when the mesh axes divide,
        "off" keeps today's replicated dispatch everywhere.
        ``scheduler`` turns on the serve engine's token-budget chunked-
        prefill scheduler (serve/scheduler.py; arch-gated by
        ``caps.supports_chunked_prefill``, fails fast here) and
        ``sched_kw`` carries its knobs (``token_budget``, ``chunk_size``,
        ``class_weights``, ``aging_ticks``).
        """
        if isinstance(arch, ModelConfig):
            if smoke:
                raise ValueError(
                    "smoke=True only applies when arch is a registry name; "
                    "pass get_smoke_config(name) directly instead")
            cfg, name = arch, arch.name
        else:
            name = arch
            cfg = get_smoke_config(arch) if smoke else get_config(arch)
        if isinstance(mesh, str):
            from repro.launch.mesh import mesh_from_spec
            mesh = mesh_from_spec(mesh)

        capacity = capacity if capacity is not None else (seq_len or 128)
        seq_len = seq_len if seq_len is not None else capacity
        axes = mesh_axes_of(mesh) if mesh is not None else {}
        if not axes and grad_sync != "flat":
            # ZeRO-1 grad layouts need a mesh to constrain against; the
            # single-device plan degenerates to the flat sync
            grad_sync = "flat"
        plan = make_plan(cfg, axes, shape_kind=shape_kind,
                         grad_sync=grad_sync, seq_len=seq_len,
                         **(plan_kw or {}))
        family = registry.resolve(cfg)
        if kv_layout not in ("dense", "paged"):
            raise ValueError(f"unknown kv_layout {kv_layout!r}; "
                             f"valid choices: dense, paged")
        if kv_layout == "paged" and \
                not family.capabilities(cfg).supports_paged_decode:
            raise ValueError(
                f"arch {cfg.name!r} does not support the paged KV layout "
                f"(caps: {family.capabilities(cfg).summary})")
        if kv_dtype not in ("f32", "int8"):
            raise ValueError(f"unknown kv_dtype {kv_dtype!r}; "
                             f"valid choices: f32, int8")
        if kv_dtype == "int8":
            if kv_layout != "paged":
                raise ValueError(
                    "kv_dtype='int8' requires kv_layout='paged' (the dense "
                    "slab cache has no quantized layout)")
            if not family.capabilities(cfg).supports_quantized_kv:
                raise ValueError(
                    f"arch {cfg.name!r} does not support the quantized KV "
                    f"pool (caps: {family.capabilities(cfg).summary})")
        if scheduler and \
                not family.capabilities(cfg).supports_chunked_prefill:
            raise ValueError(
                f"arch {cfg.name!r} does not support chunked prefill "
                f"(caps: {family.capabilities(cfg).summary}); the serve "
                f"scheduler needs a pure self-attention, non-SWA stack — "
                f"use scheduler=False")
        from repro.kernels.partition import resolve_kernel_partition
        resolve_kernel_partition(partition)    # fail fast on bad values
        return cls(arch=name, cfg=cfg, family=family, mesh=mesh, plan=plan,
                   specs=family.specs(cfg), seq_len=seq_len,
                   capacity=capacity, attn_impl=attn_impl,
                   ffn_impl=ffn_impl, kv_layout=kv_layout,
                   kv_dtype=kv_dtype,
                   partition=partition, scheduler=scheduler,
                   sched_kw=sched_kw,
                   param_dtype=param_dtype, seed=seed, params=params,
                   plan_kw=plan_kw)

    _KEEP_MESH = object()      # reshape() sentinel: None is a valid mesh

    def reshape(self, *, shape_kind: Optional[str] = None,
                mesh=_KEEP_MESH,
                seq_len: Optional[int] = None,
                capacity: Optional[int] = None, grad_sync: Optional[str] = None,
                attn_impl: Optional[str] = None,
                ffn_impl: Optional[str] = None,
                kv_layout: Optional[str] = None,
                kv_dtype: Optional[str] = None,
                partition: Optional[str] = None,
                scheduler: Optional[bool] = None,
                sched_kw: Optional[dict] = None,
                plan_kw: Optional[dict] = None) -> "Runtime":
        """A new Runtime over the same cfg/params with a re-planned fabric
        mapping (e.g. train -> decode); materialized params and the original
        plan overrides are carried over (``plan_kw`` entries merge on top).

        ``mesh`` moves the Runtime onto a different device grid — the
        elastic/evacuation path (ft/elastic.py) hands the surviving mesh
        here.  Materialized params take a host round-trip so the new
        executables re-commit them under the new mesh (their old shardings
        may reference devices that no longer participate); on a real
        cluster this is where a checkpoint restore with resharding slots
        in instead."""
        if mesh is Runtime._KEEP_MESH:
            mesh, params = self.mesh, self._params
        else:
            params = (None if self._params is None
                      else jax.tree.map(jax.device_get, self._params))
        new = Runtime.create(
            self.cfg, mesh,
            shape_kind=shape_kind if shape_kind is not None
            else self.plan.shape_kind,
            seq_len=seq_len, capacity=capacity,
            grad_sync=grad_sync if grad_sync is not None else self.plan.grad_sync,
            attn_impl=attn_impl if attn_impl is not None else self.attn_impl,
            ffn_impl=ffn_impl if ffn_impl is not None else self.ffn_impl,
            kv_layout=kv_layout if kv_layout is not None else self.kv_layout,
            kv_dtype=kv_dtype if kv_dtype is not None else self.kv_dtype,
            partition=partition if partition is not None else self.partition,
            scheduler=scheduler if scheduler is not None else self.scheduler,
            sched_kw={**self.sched_kw, **(sched_kw or {})},
            param_dtype=self.param_dtype, seed=self.seed,
            params=params, plan_kw={**self.plan_kw, **(plan_kw or {})})
        # telemetry survives the reshape: evacuation builds a new Runtime,
        # but counters must stay monotonic and the tick timeline continuous
        new._telemetry = self._telemetry
        new._link_monitor = self._link_monitor
        return new

    # -- observability -------------------------------------------------------

    def telemetry(self):
        """This Runtime's obs.Telemetry (lazy): the metrics registry +
        tracer every subsystem built on this Runtime reports into.  One
        object per Runtime lineage — :meth:`reshape` carries it over."""
        if self._telemetry is None:
            from repro.obs import Telemetry
            self._telemetry = Telemetry()
        return self._telemetry

    def link_monitor(self):
        """Continuous LinkMonitor (lazy) bound to the telemetry registry:
        burn-in sweeps and the serve engine's ``apply_link_reports`` both
        feed it; ``link_monitor().derate(plan.fabric)`` gives the
        BER-derated fabric view."""
        if self._link_monitor is None:
            from repro.core.linktest import LinkMonitor
            self._link_monitor = LinkMonitor(
                registry=self.telemetry().registry)
        return self._link_monitor

    # -- params / state -----------------------------------------------------

    @property
    def params(self):
        """Materialized params (lazy; seeded by ``seed``).  Assignable —
        e.g. trained weights or a checkpoint restore."""
        if self._params is None:
            self._params = init_params(self.specs,
                                       jax.random.PRNGKey(self.seed),
                                       self.param_dtype,
                                       shardings=self.param_shardings)
        return self._params

    @params.setter
    def params(self, value):
        self._params = value

    @property
    def params_fingerprint(self) -> int:
        """mod-2^32 checksum of the materialized params (ft/integrity.py)
        — the reference the serve engine registers at build and the
        health gate re-verifies (``HealthReason.DATA_CORRUPTION``).
        Recomputed on access: a changed value between two reads of an
        unmodified Runtime *is* the corruption signal."""
        from repro.ft import integrity as ft_integrity
        return int(jax.device_get(
            ft_integrity.tree_fingerprint_jit(self.params)))

    def init_train_state(self, key=None):
        key = jax.random.PRNGKey(self.seed) if key is None else key
        return train_state_mod.init_train_state(self.specs, key, self.plan,
                                                self.param_dtype)

    @property
    def state_shardings(self):
        """TrainState NamedSharding tree (None without a mesh)."""
        if self.mesh is None:
            return None
        return train_state_mod.train_state_shardings(
            self.specs, self.plan, self.mesh, self.param_dtype)

    @property
    def param_shardings(self):
        """Params NamedSharding tree by ``plan.param_rules`` (None without
        a mesh).  A dim its rule's mesh axes do not divide stays whole."""
        if self.mesh is None:
            return None
        sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))

        def fit(spec, pspec):
            dims = []
            for n, ax in zip(spec.shape, tuple(pspec) + (None,) * len(
                    spec.shape)):
                names = (ax,) if isinstance(ax, str) else tuple(ax or ())
                dims.append(ax if n % math.prod(sizes[a] for a in names) == 0
                            else None)
            return NamedSharding(self.mesh, PartitionSpec(*dims))

        return jax.tree.map(
            fit, self.specs, partition_specs(self.specs,
                                             self.plan.param_rules),
            is_leaf=is_pspec)

    @property
    def batch_sharding(self) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, batch_pspec(self.plan))

    @property
    def num_params(self) -> int:
        return count_params(self.specs)

    # -- step factories (un-jitted; dry-run cells + engine build on these) --

    def make_train_step(self, *, schedule=None, opt_cfg=None,
                        microbatches: int = 1) -> Callable:
        return train_steps.make_train_step(
            self.cfg, self.plan, self.specs, self.mesh, schedule=schedule,
            opt_cfg=opt_cfg, microbatches=microbatches,
            attn_impl=self.attn_impl, ffn_impl=self.ffn_impl,
            partition=self.partition)

    def make_prefill_step(self, *, capacity: Optional[int] = None) -> Callable:
        return serve_steps.make_prefill_step(
            self.cfg, self.plan, self.mesh,
            capacity=capacity if capacity is not None else self.capacity,
            attn_impl=self.attn_impl, ffn_impl=self.ffn_impl,
            partition=self.partition)

    def make_decode_step(self, *, attn_impl: Optional[str] = None,
                         advance_pos: bool = False) -> Callable:
        return serve_steps.make_decode_step(
            self.cfg, self.plan, self.mesh,
            attn_impl=attn_impl if attn_impl is not None else self.attn_impl,
            advance_pos=advance_pos, partition=self.partition)

    def make_paged_decode_step(self, *,
                               attn_impl: Optional[str] = None,
                               kv_dtype: Optional[str] = None) -> Callable:
        return serve_steps.make_paged_decode_step(
            self.cfg, self.plan, self.mesh,
            attn_impl=attn_impl if attn_impl is not None else self.attn_impl,
            partition=self.partition,
            kv_dtype=kv_dtype if kv_dtype is not None else self.kv_dtype)

    def make_mixed_step(self, *, attn_impl: Optional[str] = None) -> Callable:
        """Scheduler mixed step (decode tick + one prefill chunk), dense
        KV layout — see serve/steps.make_mixed_step."""
        return serve_steps.make_mixed_step(
            self.cfg, self.plan, self.mesh,
            attn_impl=attn_impl if attn_impl is not None else self.attn_impl,
            partition=self.partition)

    def make_paged_mixed_step(self, *,
                              attn_impl: Optional[str] = None,
                              kv_dtype: Optional[str] = None) -> Callable:
        """Scheduler mixed step, paged KV layout — see
        serve/steps.make_paged_mixed_step."""
        return serve_steps.make_paged_mixed_step(
            self.cfg, self.plan, self.mesh,
            attn_impl=attn_impl if attn_impl is not None else self.attn_impl,
            partition=self.partition,
            kv_dtype=kv_dtype if kv_dtype is not None else self.kv_dtype)

    # -- compiled executables ----------------------------------------------

    def compile_train_step(self, *, schedule=None, opt_cfg=None,
                           microbatches: int = 1, donate: bool = True):
        """Jitted (state, batch) -> (state, metrics), sharded + state-donated
        when a mesh is present."""
        step = self.make_train_step(schedule=schedule, opt_cfg=opt_cfg,
                                    microbatches=microbatches)
        donate_kw = dict(donate_argnums=(0,)) if donate else {}
        if self.mesh is None:
            return jax.jit(step, **donate_kw)
        sh = self.state_shardings
        return self._bind_mesh(jax.jit(step, in_shardings=(sh, None),
                                       out_shardings=(sh, None), **donate_kw))

    @property
    def train_step(self):
        """Default compiled train step (cosine-free constant schedule comes
        from train/steps defaults; pass your own via compile_train_step)."""
        if "train_step" not in self._exec:
            self._exec["train_step"] = self.compile_train_step()
        return self._exec["train_step"]

    def mesh_context(self):
        """Context manager binding this Runtime's mesh (nullcontext when
        single-device).  Tracing sharding-annotated model code requires an
        ambient mesh for the bare-PartitionSpec constraints; every cached
        executable and the serve engine bind it through here."""
        return self.mesh if self.mesh is not None else contextlib.nullcontext()

    def _bind_mesh(self, fn):
        """Wrap a jitted executable so each call runs under mesh_context()."""
        if self.mesh is None:
            return fn

        def bound(*args, **kwargs):
            with self.mesh_context():
                return fn(*args, **kwargs)

        return bound

    def _with_rules(self, fn):
        """Run ``fn`` under the plan's activation rules when a mesh exists;
        without one the model-level path is left bare so it is bit-for-bit
        the raw registry family surface (the parity contract
        tests/test_registry.py pins) — unless a non-default kernel impl was
        requested or decode resolves to a Pallas kernel, in which case only
        the impl-selection rules are installed (models resolve "auto" to
        the same backend either way, so parity is preserved).  The decode
        rule makes ``decode_step`` run the kernel ``describe()`` reports,
        as the serve engine's decode does."""
        decode = self.decode_attn_impl
        impls = {"train_attn_impl": self.attn_impl, "ffn_impl": self.ffn_impl,
                 "decode_attn_impl": decode}
        if self.mesh is None:
            if (self.attn_impl == "auto" and self.ffn_impl == "auto"
                    and decode == "ref"):
                return fn()
            with activation_sharding(impls):
                return fn()
        rules = dict(self.plan.act_rules)
        rules["mesh"] = self.mesh
        rules["kernel_partition"] = self.partition
        rules.update(impls)
        with activation_sharding(rules):
            return fn()

    @property
    def loss(self):
        """Jitted (batch) -> (loss, metrics) over ``rt.params``
        (override per call with ``params=``)."""
        if "loss" not in self._exec:
            fam, cfg = self.family, self.cfg

            @jax.jit
            def _loss(params, batch):
                return self._with_rules(lambda: fam.loss(params, batch, cfg))

            _loss = self._bind_mesh(_loss)
            self._exec["loss"] = \
                lambda batch, *, params=None: _loss(self._p(params), batch)
        return self._exec["loss"]

    @property
    def prefill(self):
        """Jitted (batch) -> (logits, caches) at ``capacity``; supports
        ``last_only`` / ``last_index`` like the family prefill."""
        if "prefill" not in self._exec:
            fam, cfg, cap = self.family, self.cfg, self.capacity

            def _raw(params, batch, last_index, last_only):
                return self._with_rules(lambda: fam.prefill(
                    params, batch, cfg, cap,
                    last_only=last_only, last_index=last_index))

            jfn = self._bind_mesh(jax.jit(_raw, static_argnames=("last_only",)))
            self._exec["prefill"] = (
                lambda batch, *, last_only=False, last_index=None, params=None:
                jfn(self._p(params), batch, last_index, last_only=last_only))
        return self._exec["prefill"]

    @property
    def decode_step(self):
        """Jitted (token [B,1], caches, pos [B]) -> (logits, caches)."""
        if "decode" not in self._exec:
            fam, cfg = self.family, self.cfg

            @jax.jit
            def _raw(params, token, caches, pos):
                return self._with_rules(
                    lambda: fam.decode_step(params, token, caches, cfg,
                                            pos=pos))

            _raw = self._bind_mesh(_raw)
            self._exec["decode"] = (
                lambda token, caches, pos, *, params=None:
                _raw(self._p(params), token, caches, pos))
        return self._exec["decode"]

    def _p(self, params):
        return self.params if params is None else params

    # -- serving ------------------------------------------------------------

    def engine(self, *, num_slots: int = 4, capacity: Optional[int] = None,
               max_admit: Optional[int] = None,
               attn_impl: Optional[str] = None, donate: bool = True,
               params=None, kv_layout: Optional[str] = None,
               kv_dtype: Optional[str] = None, **engine_kw):
        """A continuous-batching ServeEngine over this Runtime.

        ``kv_layout`` and ``kv_dtype`` default to the Runtime's own knobs;
        ``engine_kw``
        forwards the paged-pool sizing (``block_size``, ``num_blocks``,
        ``max_blocks_per_seq``, ``admit_window``), the scheduler knobs
        (``scheduler``, ``token_budget``, ``chunk_size``,
        ``class_weights``, ``aging_ticks`` — defaulting to this Runtime's
        ``scheduler``/``sched_kw``) and the fault-tolerance knobs
        (``health_every``, ``injector``, ``tick_retries``,
        ``retry_backoff_s``, ``straggler_kw``, ``max_evacuations``)."""
        from repro.serve.engine import ServeEngine
        return ServeEngine(self, num_slots=num_slots, capacity=capacity,
                           max_admit=max_admit, attn_impl=attn_impl,
                           donate=donate, params=params,
                           kv_layout=kv_layout, kv_dtype=kv_dtype,
                           **engine_kw)

    def kv_bytes_per_stream(self, kv_dtype: Optional[str] = None, *,
                            block_size: int = 16) -> int:
        """Per-stream KV byte budget at ``capacity`` under this Runtime's
        serve layout: attention layers × 2 (K+V) × capacity × KV × Dh ×
        itemsize, plus the two f32 per-(block, kv-head) scale pools
        (amortized over ``block_size`` — the engine's default) under
        ``kv_dtype="int8"``.  Exact for the dense slab; for paged pools it
        is the per-entry cost × capacity (block-granularity rounding and
        prefix sharing move the realized number — the engine's
        ``kv_cache_bytes()`` reports that)."""
        kv_dtype = kv_dtype if kv_dtype is not None else self.kv_dtype
        cfg = self.cfg
        attn_layers = sum(
            g.repeats * sum(1 for k in g.pattern
                            if k.startswith("attn") and k != "attn_cross")
            for g in cfg.groups)
        itemsize = 1 if kv_dtype == "int8" else jnp.dtype(cfg.dtype).itemsize
        per_entry = 2 * cfg.num_kv_heads * cfg.head_dim * itemsize
        total = attn_layers * self.capacity * per_entry
        if kv_dtype == "int8":           # f32 per-(block, kv-head) scales
            blocks = -(-self.capacity // block_size)
            total += attn_layers * blocks * 2 * cfg.num_kv_heads * 4
        return total

    # -- qualification ------------------------------------------------------

    def burn_in(self, *, mem_bytes: int = 1 << 22,
                link_payload: int = 1 << 16,
                ber_threshold: float = 0.0):
        """Full hardware qualification (paper: DDR soak + IBERT PRBS
        sweep): memory-test every mesh device and PRBS-sweep every axis.
        The report is stored and surfaced by :meth:`describe`; its
        ``axis_ber`` feeds ``Fabric.with_link_ber`` and the serve
        engine's ``apply_link_reports`` gate."""
        from repro.launch.preflight import run_burn_in
        self._burn_in = run_burn_in(
            self.mesh, mem_bytes=mem_bytes, link_payload=link_payload,
            ber_threshold=ber_threshold)
        if self._burn_in.links:
            # the qualification sweep is the link monitor's first sample
            self.link_monitor().record(self._burn_in.links)
        return self._burn_in

    # -- report -------------------------------------------------------------

    @property
    def decode_attn_impl(self) -> str:
        """The decode-attention backend the serve path will actually use
        (env override + capability fallback + kv_layout applied now)."""
        return serve_steps.resolve_decode_attn_impl(
            self.attn_impl, self.cfg, kv_layout=self.kv_layout,
            kv_dtype=self.kv_dtype)

    @property
    def train_attn_impl(self) -> str:
        """The train/prefill attention backend this Runtime will actually
        use (env override + capability fallback applied now; per-call shape
        eligibility is still re-checked at trace time)."""
        from repro.kernels import ops as kernel_ops
        impl = kernel_ops.resolve_train_attn_impl(self.attn_impl)
        if impl == "pallas" and not self.caps.supports_flash_train:
            impl = "ref"
        return impl

    @property
    def fused_ffn_impl(self) -> str:
        """The dense-FFN backend this Runtime will actually use (env
        override + capability fallback applied now)."""
        from repro.kernels import ops as kernel_ops
        impl = kernel_ops.resolve_ffn_impl(self.ffn_impl)
        if impl == "pallas" and not self.caps.supports_fused_ffn:
            impl = "ref"
        return impl

    def _ft_status(self) -> str:
        """Fault-tolerance posture: device pool, the mesh a one-device
        loss would evacuate onto (ft/elastic.best_mesh_shape with the TP
        axis preserved), and any armed REPRO_FAULT_PLAN."""
        import os
        from repro.ft.elastic import best_mesh_shape
        n_dev = (int(self.mesh.devices.size) if self.mesh is not None else 1)
        tp = self.plan.tp_size
        if n_dev - 1 >= tp:
            shape = best_mesh_shape(n_dev - 1, model_size=tp,
                                    prefer_pods=self.plan.mesh_axes.get(
                                        "pod", 1))
            lose1 = "x".join(str(s) for s in shape)
        else:
            lose1 = "impossible (survivors < TP group)"
        plan_env = os.environ.get("REPRO_FAULT_PLAN", "").strip() or "none"
        if self._burn_in is not None:
            b = self._burn_in
            burn = (f"{'PASS' if b.ok else 'FAIL'} "
                    f"(mem {sum(m.ok for m in b.mem)}/{len(b.mem)}, "
                    + (f"links {sum(l.ok for l in b.links)}/{len(b.links)}, "
                       f"worst BER<"
                       f"{max(l.ber_bound for l in b.links):.0e}"
                       if b.links else "no mesh axes") + ")")
        else:
            burn = "not run (Runtime.burn_in() / serve --burn-in)"
        return (f"  ft        : devices={n_dev} tp={tp} "
                f"evac(lose-1)->{lose1} fault_plan={plan_env}\n"
                f"  burn-in   : {burn}")

    def describe(self) -> str:
        """Plan + tier placement + kernel selection in one report."""
        from repro.kernels import ops as kernel_ops
        plan = self.plan
        tiers = ", ".join(
            f"{ax}({sz})->{plan.fabric.axis_tier.get(ax, 'local')}"
            for ax, sz in plan.mesh_axes.items()) or "single-device"
        train_attn, ffn = self.train_attn_impl, self.fused_ffn_impl
        decode_attn = self.decode_attn_impl
        for op, impl in (("train_attn", train_attn), ("ffn", ffn),
                         ("decode_attn", decode_attn)):
            kernel_ops.log_impl_selection(op, impl, detail=self.cfg.name)
        lines = [
            f"runtime[{self.cfg.name}] family={self.family.name} "
            f"params={self.num_params:,}",
            f"  caps      : {self.caps.summary}",
            f"  tiers     : {tiers} (fabric {plan.fabric.name})",
            topology.describe(plan),
            f"  kernels   : train_attn={train_attn} ffn={ffn} "
            f"decode_attn={decode_attn} "
            f"(requested attn={self.attn_impl} ffn={self.ffn_impl}); "
            f"flash_train_ok={self.caps.supports_flash_train} "
            f"fused_ffn_ok={self.caps.supports_fused_ffn} "
            f"flash_decode_ok={self.caps.supports_flash_decode} "
            f"paged_decode_ok={self.caps.supports_paged_decode}",
            f"  serve     : capacity={self.capacity} "
            f"kv_layout={self.kv_layout} kv_dtype={self.kv_dtype} "
            f"kv_bytes/stream={self.kv_bytes_per_stream():,} "
            f"swa_bucketing={'exact' if self.caps.swa else 'pow2'} "
            + ("scheduler[" + ", ".join(
                   f"{k}={v}" for k, v in sorted(self.sched_kw.items()))
               + ("]" if self.sched_kw else "defaults]")
               if self.scheduler else "scheduler=off")
            + f" chunked_prefill_ok={self.caps.supports_chunked_prefill}",
            self._ft_status(),
            "  obs       : " + (self._telemetry.describe()
                                if self._telemetry is not None
                                else "not wired (Runtime.telemetry())")
            + (" | " + self._link_monitor.describe()
               if self._link_monitor is not None else ""),
        ]
        from repro.kernels import partition as kernel_partition
        pspecs = kernel_partition.partition_report(self.cfg, plan, self.caps,
                                                   self.partition)
        lines.append("  partition : " + "; ".join(
            f"{k}[{v}]" for k, v in pspecs.items()))
        return "\n".join(lines)

    def __repr__(self) -> str:
        return (f"Runtime({self.cfg.name!r}, family={self.family.name!r}, "
                f"shape_kind={self.plan.shape_kind!r}, "
                f"mesh={self.plan.mesh_axes})")
