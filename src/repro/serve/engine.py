"""Continuous-batching serve engine.

A fixed pool of ``num_slots`` decode slots runs in lock-step (one jitted
decode step per tick).  Requests are admitted into free slots via batched
prefill, finished sequences (EOS or max_tokens) free their slot.  This is
the vLLM-style iteration-level scheduler reduced to its JAX-native core:
static shapes (slot-padded), no re-compilation when the working set
changes.

The engine is deliberately host-driven — admission and eviction are Python;
only the hot loop (decode step over all slots) is jitted.  Inactive slots
still compute: their outputs are discarded and their cache writes are junk
that attends to nothing (the entries' positions exceed every live query)
and is fully overwritten by the admission splice when the slot is reused.

Serving fast path
-----------------

The data path is built for throughput; four mechanisms keep the device hot
and the host off the critical path:

* **Donated in-place state.**  The decode step and the admission splice are
  jitted with ``donate_argnums`` on the slot-stacked cache pytree, and the
  splice writes each admitted row with ``lax.dynamic_update_slice`` — XLA
  updates the donated buffers in place, so admission costs O(slot), not
  O(num_slots x capacity), and the per-tick cache update never copies the
  pool.
* **Batched, bucketed admission.**  Up to ``max_admit`` queued requests are
  admitted per prefill call: consecutive same-bucket prompts are right-padded
  to a power-of-two bucket length (capped at ``capacity``) and run through
  one padded-batch prefill; the admission batch itself is padded to a
  power-of-two row count by repeating the last request, so compilation count
  is bounded by O(log buckets x log num_slots).  SWA (ring-buffer) archs use
  exact prompt lengths as buckets — right-padding past the window would trim
  real entries out of the ring.  Pad rows/columns are invalidated in the
  cache (``kvcache.mask_prefill_pos``), and next tokens come from each row's
  true last position (``last_index``).  Note the standard continuous-
  batching caveat: batch-coupled compute (MoE expert-capacity drops) can
  make a request's tokens depend on what it was admitted or decoded with —
  true of every lock-step decode tick already, now of admission too.
* **Async token collection.**  Tokens and positions are device-resident
  int32 arrays advanced inside the jitted step; the device->host transfer is
  double-buffered: each tick dispatches decode step *t*, then
  ``jax.device_get``s step *t-1*'s tokens while *t* runs.  EOS/max_tokens
  detection therefore lags one tick; the extra speculative token of a
  finished slot is discarded at collection (``Request.done`` guard) and the
  slot's junk writes are fully overwritten at re-admission.
* **Kernel fallback rules.**  Decode attention resolves via
  ``steps.resolve_decode_attn_impl``: the Pallas flash-decode kernel on
  TPU-capable backends, the reference jnp softmax elsewhere (or when the
  arch needs logit softcap / the cache length doesn't block evenly);
  ``REPRO_DECODE_ATTN=pallas|ref|paged`` overrides.

Paged KV layout
---------------

``kv_layout="paged"`` (arch-gated by ``caps.supports_paged_decode``)
replaces the per-slot dense slabs with a pooled block cache
(serve/blockpool.py): K/V live in ``[num_blocks, block_size, KV, Dh]``
tensors shared by every slot, each slot follows an int32 block table, and
HBM scales with *actual* sequence lengths instead of ``num_slots x
capacity``.  The engine mechanics are unchanged — same ``tick()`` loop,
same donated in-place updates, same bucketed admission — with three paged
twists:

* **Admission** allocates each request's block chain (full prompt blocks
  are content-hashed, so identical prefixes share physical blocks — also
  across an eviction, since freed blocks keep their registration until
  recycled) and splices the prefill caches in with one scatter per bucket
  column (``blockpool.paged_splice``; shared blocks skip their write).
* **Decode** carries a per-tick write plan: the host walks the active
  slots, lazily growing each chain at block boundaries and resolving
  copy-on-write for shared tails (``BlockPool.write_plan``), then passes
  the table + per-slot write blocks to the jitted step.  Inactive slots
  write to the reserved trash block and gather the permanently-empty null
  block — their junk stays unobservable.
* **Eviction** just drops refcounts; blocks return to the free list when
  the last owner leaves.

Fault tolerance
---------------

The paper's MCM is validated by adversarial stress (PRBS link tests,
exhaustive memory tests) because degradation at scale is a *when*, not an
*if*; the engine carries the same posture one level up.  Three watchdogs
wrap the tick loop, and every escalation converges on live evacuation:

* **Health-gated ticks.**  Every ``health_every`` ticks the engine runs
  ``ft.health.check_devices`` (cached-checksum proof-of-work) over its
  mesh devices; any unhealthy report — structured ``HealthReason``, no
  string parsing — escalates straight to evacuation with the failed
  devices excluded.
* **Straggler escalation (opt-in).**  Per-tick wall times (dispatch + the
  overlapped collection) always feed a ``StragglerMonitor``'s histograms;
  only an engine built with ``straggler_kw`` acts on them, mapping the
  warn -> remesh -> abort ladder to log -> evacuate -> evacuate (with
  scripted-fault device attribution when available, else an in-place
  rebuild).  Off by default: a tick that compiles a new program shape is
  slow by design, and a wall-clock ladder would read it as a straggler.
* **Bounded retry.**  A tick that *raises* is retried with exponential
  backoff up to ``tick_retries`` times — transient faults recover without
  losing a stream — before escalating to evacuation.  A step that fails
  to trace, lower or compile is a program error, not a fault: every
  model step is compiled ahead of its first run for each input signature
  (``_Step``), and a refusal raises :class:`StepCompileError` at once —
  never retried, never evacuated.

**Evacuation** (``_evacuate``) never drops a stream: the in-flight token
transfer is flushed, every live request's portable state is snapshotted
(tokens emitted, position, and — under the paged layout — its block
chain, the host-side KV identity), the generated prefix is folded into
the prompt, the Runtime is ``reshape()``-d onto the surviving mesh
(``ft.elastic.evacuation_mesh`` preserves the TP axis; params take a host
round-trip), the data path is rebuilt, and the snapshot re-enters through
the standard prefill admission at the head of the queue.  Replaying
prompt+generated through prefill computes the next token at exactly the
position the lost decode step would have, so the continued stream is the
same f32 token sequence the uninterrupted run emits (the contract
tests/test_ft_serve.py pins, dense and paged).  Under the paged layout
the replayed prefixes re-register in the block pool's content cache, so
streams that shared prefix blocks before the failure share them again
after — the paged KV-replay fast path.

Deterministic fault injection (``ft/inject.py``; ``REPRO_FAULT_PLAN``)
scripts device failures, stalls and mid-tick raises at chosen tick
numbers, which is how all of the above is exercised on the CPU mesh.
``snapshot()`` / ``load_snapshot()`` extend the same replay contract to a
``checkpoint``-backed warm restart across engine (or process) lifetimes.

Data integrity
--------------

``scrub_every > 0`` arms the silent-data-corruption layer
(ft/integrity.py) — the serving analog of the paper's DDR memory tests
and PRBS link qualification, because a flipped KV bit serves garbage
without raising anything:

* **Sealing.**  Every scrub tick the engine fingerprints the *written*
  span of each tracked region — pool blocks (paged) or slot rows (dense,
  non-SWA) — with one jitted masked reduction over the whole cache, and
  records a params checksum at build.  Decode/prefill only ever append
  past a seal (allocation generations catch recycling), so a seal
  mismatch at the next scrub is corruption, not progress.
* **Detection.**  The scrub re-verifies every seal at its *recorded*
  extent; the health gate re-verifies the params checksum
  (``HealthReason.DATA_CORRUPTION``); the device->host token payload
  carries a device-computed checksum the collector re-derives on the host
  copy — a mismatch is a corrupt transfer, retried from the still-
  resident device array, so a corrupted payload is never applied.
* **Recovery.**  Corrupted blocks are quarantined (``BlockPool.poison``:
  off the prefix cache and the free list until wiped clean on a later
  scrub); only the *affected* streams roll back to their last verified
  token, fold, and replay through standard prefill admission — per-stream
  quarantine-and-replay, no mesh rebuild.  Corrupted params restore from
  the build-time backup (the checkpoint stand-in) and every live stream
  replays, since KV appended under corrupted params is garbage with a
  valid seal.

With ``scrub_every=1`` the detection point sits between a corrupted
dispatch and its (double-buffered) collection, so zero corrupted tokens
are ever emitted; coarser cadences trade detection latency for scrub
cost, bounded by the per-request ``verified`` watermark rollback.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import EngineSnapshot
from repro.core.linktest import LinkMonitor
from repro.ft import elastic as ft_elastic
from repro.ft import health as ft_health
from repro.ft import integrity as ft_integrity
from repro.ft.inject import FaultInjector
from repro.ft.straggler import StragglerMonitor
from repro.models.attention import PAD_POS
from repro.obs import Telemetry
from repro.obs.metrics import latency_fields
from repro.serve import blockpool, kvcache
from repro.serve.scheduler import Scheduler

_FROM_ENV = object()     # injector default: build from REPRO_FAULT_PLAN


@dataclass
class Request:
    rid: int
    prompt: np.ndarray               # [S] int32
    max_new_tokens: int = 16
    eos_id: int = -1                 # -1 = never
    priority: int = 0                # scheduler class (lower id != higher
    #                                  priority; weights are per-class knobs)
    # filled by the engine
    generated: list = field(default_factory=list)
    submitted_at: float = 0.0
    admitted_at: float = 0.0         # queue exit (prefill start)
    first_token_at: float = 0.0
    finished_at: float = 0.0
    token_times: list = field(default_factory=list)   # decode-token arrivals
    done: bool = False
    # replay bookkeeping: how many ``generated`` tokens are already folded
    # into ``prompt`` (evacuation / snapshot re-prefill the folded prefix;
    # the counter makes folding idempotent across repeated evacuations)
    folded: int = 0
    # integrity watermark: tokens verified against clean state at the last
    # scrub — a corruption rollback truncates ``generated`` here (never
    # below ``folded``: those tokens already live inside the prompt)
    verified: int = 0


_STAT_NAMES = ("ticks", "tokens_out", "admitted", "finished",
               "prefill_calls", "chunk_ticks", "evacuations", "tick_retries",
               "health_checks", "scrubs", "corruption_detected",
               "kv_quarantined", "streams_replayed", "params_restores",
               "transfer_retries")


@dataclass
class EngineStats:
    """Engine counters.  The public shape is the plain dataclass every
    caller reads (``eng.stats.finished``); :meth:`bind` additionally backs
    each field with a monotonic registry Counter
    (``serve_engine_<field>_total``), so one metrics snapshot carries them
    and the instrument itself enforces that no retry/evacuation/replay
    path ever double-counts backwards.  The registry survives an
    evacuation's Runtime reshape, so counters accumulate across engine
    lifetimes; each binding records its base offset so the dataclass view
    stays per-engine."""

    ticks: int = 0
    tokens_out: int = 0
    admitted: int = 0
    finished: int = 0
    prefill_calls: int = 0
    chunk_ticks: int = 0     # scheduler: mixed (decode + chunk) ticks
    # fault tolerance
    evacuations: int = 0
    tick_retries: int = 0
    health_checks: int = 0
    # data integrity (scrub_every > 0)
    scrubs: int = 0
    corruption_detected: int = 0   # detection events (kv regions + params
    #                                restores + collective mismatches)
    kv_quarantined: int = 0        # pool blocks poisoned / dense rows hit
    streams_replayed: int = 0      # streams rolled back + requeued
    params_restores: int = 0
    transfer_retries: int = 0      # device->host payload re-fetches

    def bind(self, registry):
        counters, base = {}, {}
        for k in _STAT_NAMES:
            c = registry.counter(f"serve_engine_{k}_total",
                                 f"cumulative engine {k}")
            counters[k] = c
            base[k] = c.value - getattr(self, k)
        object.__setattr__(self, "_bound", (counters, base))

    def __setattr__(self, name, value):
        bound = getattr(self, "_bound", None)
        if bound is not None and name in bound[0]:
            # mirror first: Counter.set raises on a decrease, so a
            # would-be regression never lands in the dataclass either
            counters, base = bound
            counters[name].set(base[name] + value)
        object.__setattr__(self, name, value)

    @property
    def summary(self) -> str:
        s = (f"ticks={self.ticks} tokens={self.tokens_out} "
             f"admitted={self.admitted} finished={self.finished} "
             f"prefills={self.prefill_calls}")
        if self.chunk_ticks:
            s += f" chunk_ticks={self.chunk_ticks}"
        if self.evacuations or self.tick_retries or self.health_checks:
            s += (f" evacuations={self.evacuations} "
                  f"retries={self.tick_retries} "
                  f"health_checks={self.health_checks}")
        if self.scrubs or self.corruption_detected:
            s += (f" scrubs={self.scrubs} "
                  f"corruption_detected={self.corruption_detected} "
                  f"quarantined={self.kv_quarantined} "
                  f"replayed={self.streams_replayed}")
        return s


def _fold_replay_prefix(req: Request):
    """Fold a request's generated tokens into its prompt so one prefill
    replays the full prefix.  After folding, re-admission through the
    standard prefill path computes the next token at position
    ``len(prompt)`` — exactly where the interrupted decode loop would have
    — so the continued stream matches the uninterrupted one.  Idempotent
    via ``Request.folded`` (repeated evacuations fold only the new tail)."""
    fresh = req.generated[req.folded:]
    if fresh:
        req.prompt = np.concatenate([np.asarray(req.prompt, np.int32),
                                     np.asarray(fresh, np.int32)])
        req.folded = len(req.generated)


def _seed_hot_loop(slots, tok, pos, next_tok, lengths):
    """Seed the device-resident token/position arrays for admitted slots.
    Every write is a dynamic_update_slice so XLA aliases in place; reverse
    order makes duplicate slot ids (trailing pad rows) resolve to the
    authentic row."""
    for i in reversed(range(slots.shape[0])):
        tok = jax.lax.dynamic_update_slice(
            tok, next_tok[i:i + 1][:, None], (slots[i], 0))
        pos = jax.lax.dynamic_update_slice(
            pos, lengths[i:i + 1].astype(pos.dtype), (slots[i],))
    return tok, pos


def _park_pos(pos, slot):
    """Park one slot's device position at the PAD_POS sentinel (scheduler
    mode): the lock-step decode keeps computing over every slot, but a
    parked slot's cache write is an out-of-bounds scatter XLA drops — a
    prefilling slot's incrementally built row is never clobbered by the
    junk the monolithic engine relies on full-row admission splices to
    overwrite."""
    return pos.at[slot].set(PAD_POS)


def _install_admitted(caches, part, slots, tok, pos, next_tok, lengths):
    """Jitted admission install: splice prefill caches into their slots and
    seed the device-resident token/position arrays.  ``caches`` is donated
    by the caller's jit wrapper; every write is a dynamic_update_slice so
    XLA aliases in place.  Reverse order mirrors kvcache.splice_slots
    (trailing rows are pad duplicates)."""
    caches = kvcache.splice_slots(caches, part, slots)
    tok, pos = _seed_hot_loop(slots, tok, pos, next_tok, lengths)
    return caches, tok, pos


def _install_admitted_paged(caches, part, dst, slots, tok, pos, next_tok,
                            lengths):
    """Paged admission install: scatter the prefill caches into their pool
    blocks (``dst`` [Bp, nb] per-column destinations; shared/pad columns
    point at the trash block) and seed the hot-loop arrays.  ``caches`` is
    donated by the caller's jit wrapper."""
    caches = blockpool.paged_splice(caches, part, dst)
    tok, pos = _seed_hot_loop(slots, tok, pos, next_tok, lengths)
    return caches, tok, pos


class StepCompileError(RuntimeError):
    """An engine step failed to trace, lower or compile for its inputs."""


class _Step:
    """A jitted engine step, compiled ahead of its first run for each input
    signature (tree structure + leaf shape/dtype/sharding) under the
    Runtime's mesh context.  A refusal raises :class:`StepCompileError`
    from the compile, so the tick loop can tell it from a dispatch
    failure."""

    def __init__(self, name: str, jitted, mesh_context):
        self.name = name
        self._jit = jitted
        self._mesh_context = mesh_context
        self._exe: dict = {}

    def __call__(self, *args):
        leaves, tree = jax.tree.flatten(args)
        key = (tree, tuple((np.shape(x), getattr(x, "dtype", type(x)),
                            getattr(x, "sharding", None)) for x in leaves))
        exe = self._exe.get(key)
        if exe is None:
            try:
                with self._mesh_context():
                    exe = self._jit.lower(*args).compile()
            except Exception as e:
                raise StepCompileError(
                    f"engine step {self.name!r} failed to compile: "
                    f"{type(e).__name__}: {e}") from e
            self._exe[key] = exe
        return exe(*args)

    def _cache_size(self) -> int:
        """Programs compiled so far (one per input signature), the same
        count ``jax.jit``'s own cache reports."""
        return len(self._exe)


class ServeEngine:
    """Continuous-batching engine over a ``repro.runtime.Runtime``.

    The Runtime owns arch/plan/mesh/params and the step factories; the
    engine owns slots, admission and the device-resident hot loop.
    ``capacity`` / ``attn_impl`` / ``params`` default to the Runtime's own
    (``params=`` lets quickstarts serve freshly trained weights).

    Fault-tolerance knobs: ``health_every`` gates ticks on device health
    checks (0 = off), ``tick_retries``/``retry_backoff_s`` bound the
    transient-failure retry loop, ``injector`` takes a ``FaultInjector``
    (defaults to parsing ``REPRO_FAULT_PLAN``; pass ``None`` to disable),
    ``straggler_kw`` arms wall-clock straggler escalation with those
    StragglerMonitor thresholds (None = observe only), and
    ``max_evacuations`` is the give-up bound on repeated evacuation (a
    persistently failing data path must eventually surface, not loop).

    ``scrub_every`` arms the data-integrity layer (0 = off): KV seals are
    re-verified every that many ticks, the params checksum is registered
    at build (re-verified by scrub and health gate), and the device->host
    token payload is checksummed per tick — see the module docstring's
    "Data integrity" section for the detect/quarantine/replay contract."""

    def __init__(self, runtime, *, num_slots: int = 4,
                 capacity: Optional[int] = None,
                 max_admit: Optional[int] = None,
                 attn_impl: Optional[str] = None, donate: bool = True,
                 params=None, kv_layout: Optional[str] = None,
                 kv_dtype: Optional[str] = None,
                 block_size: Optional[int] = None,
                 num_blocks: Optional[int] = None,
                 max_blocks_per_seq: Optional[int] = None,
                 admit_window: Optional[int] = None,
                 scheduler: Optional[bool] = None,
                 token_budget: Optional[int] = None,
                 chunk_size: Optional[int] = None,
                 class_weights: Optional[dict] = None,
                 aging_ticks: Optional[int] = None,
                 health_every: int = 0, injector=_FROM_ENV,
                 tick_retries: int = 2, retry_backoff_s: float = 0.02,
                 straggler_kw: Optional[dict] = None,
                 max_evacuations: int = 8,
                 scrub_every: int = 0,
                 trace: Optional[bool] = None):
        rt = runtime
        self.rt = rt
        self.caps = rt.caps
        # observability: the Runtime's shared registry + tracer (survives
        # the reshape an evacuation performs — the engine keeps its own
        # reference so instruments also survive a data-path rebuild).
        # ``trace=True/False`` flips span recording; None leaves the
        # shared tracer as it is (disabled by default).
        self.obs = (rt.telemetry() if hasattr(rt, "telemetry")
                    else Telemetry())
        self.tracer = self.obs.tracer
        if trace is not None:
            self.tracer.enabled = bool(trace)
        self._init_instruments()
        self.params = params if params is not None else rt.params
        capacity = capacity if capacity is not None else rt.capacity
        self.num_slots, self.capacity = num_slots, capacity
        self.max_admit = max_admit if max_admit is not None else num_slots
        # bounded queue-scan window for admission grouping (see _admit_batch)
        self.admit_window = (admit_window if admit_window is not None
                             else 4 * self.max_admit)
        kv_layout = (kv_layout if kv_layout is not None
                     else getattr(rt, "kv_layout", "dense"))
        if kv_layout not in ("dense", "paged"):
            raise ValueError(f"unknown kv_layout {kv_layout!r}; "
                             f"valid choices: dense, paged")
        if kv_layout == "paged" and not self.caps.supports_paged_decode:
            raise ValueError(
                f"arch {rt.cfg.name!r} does not support the paged KV "
                f"layout (caps: {self.caps.summary}); use kv_layout='dense'")
        if kv_layout == "dense" and any(
                v is not None for v in (block_size, num_blocks,
                                        max_blocks_per_seq)):
            raise ValueError(
                "block_size/num_blocks/max_blocks_per_seq size the paged "
                "block pool; pass kv_layout='paged' (a dense engine would "
                "silently ignore them)")
        self.kv_layout = kv_layout
        self.paged = kv_layout == "paged"
        # quantized paged pool: int8 blocks + per-(entry, kv-head) scales,
        # dequantized inside the decode kernel (full-precision KV never
        # exists in HBM after admission)
        kv_dtype = (kv_dtype if kv_dtype is not None
                    else getattr(rt, "kv_dtype", "f32"))
        if kv_dtype not in ("f32", "int8"):
            raise ValueError(f"unknown kv_dtype {kv_dtype!r}; "
                             f"valid choices: f32, int8")
        if kv_dtype == "int8":
            if not self.paged:
                raise ValueError(
                    "kv_dtype='int8' requires kv_layout='paged' (the dense "
                    "slab cache has no quantized layout)")
            if not self.caps.supports_quantized_kv:
                raise ValueError(
                    f"arch {rt.cfg.name!r} does not support the quantized "
                    f"KV pool (caps: {self.caps.summary}); use "
                    f"kv_dtype='f32'")
        self.kv_dtype = kv_dtype
        self.quantized = kv_dtype == "int8"
        # chunked-prefill scheduler (serve/scheduler.py): knobs default to
        # the Runtime's scheduler/sched_kw so Runtime.create(scheduler=True)
        # flows through engine() untouched
        self.scheduler = (scheduler if scheduler is not None
                          else getattr(rt, "scheduler", False))
        if self.scheduler and not self.caps.supports_chunked_prefill:
            raise ValueError(
                f"arch {rt.cfg.name!r} does not support chunked prefill "
                f"(caps: {self.caps.summary}); the scheduler needs a pure "
                f"self-attention, non-SWA stack — use scheduler=False")
        if not self.scheduler and any(
                v is not None for v in (token_budget, chunk_size,
                                        class_weights, aging_ticks)):
            raise ValueError(
                "token_budget/chunk_size/class_weights/aging_ticks tune the "
                "chunked-prefill scheduler; pass scheduler=True (a "
                "monolithic engine would silently ignore them)")
        if self.scheduler:
            skw = dict(getattr(rt, "sched_kw", None) or {})
            for k, v in (("token_budget", token_budget),
                         ("chunk_size", chunk_size),
                         ("class_weights", class_weights),
                         ("aging_ticks", aging_ticks)):
                if v is not None:
                    skw[k] = v
            self.sched = Scheduler(registry=self.obs.registry, **skw)
            if self.sched.chunk_size > capacity:
                raise ValueError(
                    f"chunk_size={self.sched.chunk_size} exceeds the decode "
                    f"capacity {capacity}")
        else:
            self.sched = None
        # data-path build knobs, kept so an evacuation-time rebuild sizes
        # the new pool/caches identically to the originals
        self._attn_impl = attn_impl
        self._donate = donate
        self._block_size = block_size if block_size is not None else 16
        self._num_blocks = num_blocks
        self._max_blocks_per_seq = max_blocks_per_seq
        # data integrity: scrub cadence (0 = off); SWA's ring buffer
        # legitimately rewrites sealed entries, so dense SWA archs cannot
        # carry KV seals (paged already excludes SWA)
        if scrub_every and self.caps.swa:
            raise ValueError(
                f"arch {rt.cfg.name!r} uses a sliding-window (ring-buffer) "
                f"KV cache whose in-place rewrites are indistinguishable "
                f"from corruption; scrub_every needs a non-SWA arch")
        self.scrub_every = scrub_every
        # fault tolerance: watchdogs + scripted-fault harness
        self.health_every = health_every
        self.injector = (FaultInjector.from_env() if injector is _FROM_ENV
                         else injector)
        self.tick_retries = tick_retries
        self.retry_backoff_s = retry_backoff_s
        self.max_evacuations = max_evacuations
        # Tick times always feed the monitor's histograms; only an
        # explicit ``straggler_kw`` lets its ladder evacuate.  The observe-
        # only thresholds are serving-tuned: decode ticks are short and
        # noisy on a shared host, so ratios sit far above the training
        # defaults.
        self._straggler_escalates = straggler_kw is not None
        self.straggler = StragglerMonitor(registry=self.obs.registry, **(
            straggler_kw if straggler_kw is not None
            else dict(window=32, warn_ratio=4.0, remesh_ratio=10.0,
                      abort_ratio=100.0, sustained=3)))
        # continuous link monitor (IBERT analog): apply_link_reports feeds
        # it, rolling per-axis BER/bandwidth gauges land in the registry
        # and ``linkmon.derate(fabric)`` applies with_link_ber
        self.linkmon = (rt.link_monitor() if hasattr(rt, "link_monitor")
                        else LinkMonitor(registry=self.obs.registry))
        self.ft_events: list[dict] = []    # structured fault-handling log
        self._tick_no = 0                  # absolute tick count (fault plans
        #                                    address ticks by this number)
        # engine state that survives an evacuation rebuild
        self.queue: deque[Request] = deque()
        self.finished: list[Request] = []
        self.stats = EngineStats()
        self.stats.bind(self.obs.registry)
        # integrity state that survives a rebuild: params checksum +
        # restore source, and injection timestamps (detection latency)
        self._params_fp: Optional[int] = None
        self._params_backup = None
        self._last_inject: dict = {}
        self._build_data_path()
        if self.scrub_every:
            self._register_params_integrity()

    def _init_instruments(self):
        """Register the engine's gauges/histograms once.  Counters backing
        ``EngineStats`` bind separately (``stats.bind``); these cover the
        point-in-time and distribution signals one snapshot should carry
        alongside them."""
        reg = self.obs.registry
        self._g_queue = reg.gauge(
            "serve_queue_depth", "requests waiting for admission")
        self._g_active = reg.gauge(
            "serve_active_slots", "slots decoding this tick")
        self._h_health = reg.histogram(
            "ft_health_check_seconds", "device health-gate latency")
        self._h_evac = reg.histogram(
            "ft_evacuation_seconds", "live evacuation latency")
        self._h_detect = reg.histogram(
            "ft_corruption_detect_ticks",
            "corruption detection latency in ticks since injection",
            buckets=(0, 1, 2, 4, 8, 16, 32, 64))
        self._c_events = reg.counter(
            "serve_ft_events_total", "structured fault-handling events",
            labels=("event",))
        # quantized-KV observability: pool footprint vs what the same
        # entries would cost at full precision, and the cumulative count of
        # pool blocks the decode kernels dequantized in-loop
        self._g_kv_bytes = reg.gauge(
            "blockpool_kv_pool_bytes",
            "bytes of KV pool storage as allocated (incl. scale pools)")
        self._g_kv_f32_bytes = reg.gauge(
            "blockpool_kv_pool_f32_equiv_bytes",
            "bytes the same KV pool entries would cost at full precision")
        self._c_dequant = reg.counter(
            "serve_kv_dequant_blocks_total",
            "pool blocks dequantized in-loop by decode dispatches")

    def _build_data_path(self):
        """(Re)build everything derived from the Runtime: jitted
        executables, device caches, block pool and slot state.  Called at
        construction and again after an evacuation has reshaped the
        Runtime onto a surviving mesh; queue/finished/stats and the
        fault-tolerance state deliberately survive the rebuild."""
        rt = self.rt
        self.cfg, self.plan, self.mesh = rt.cfg, rt.plan, rt.mesh
        self._devices = (list(self.mesh.devices.flatten())
                         if self.mesh is not None else jax.devices()[:1])
        donate_kw = dict(donate_argnums=(2,)) if self._donate else {}
        splice_kw = dict(donate_argnums=(0,)) if self._donate else {}
        # One capacity-padded prefill for both layouts: the paged splice
        # reads block columns out of the same program's caches, so dense
        # and paged engines see bitwise-identical prefill K/V (the
        # token-parity contract tests/test_paged.py pins down).
        # Model steps are ``_Step``s: compiled ahead of each new input
        # signature under the Runtime's mesh context (sharding-annotated
        # model code needs an ambient mesh for its bare-PartitionSpec
        # constraints), so a compile refusal never looks like a fault.
        def step(name, fn, **kw):
            return _Step(name, jax.jit(fn, **kw), rt.mesh_context)

        self._prefill = step("prefill",
                             rt.make_prefill_step(capacity=self.capacity))
        if self.paged:
            # block pool sized for the worst case (every slot at capacity)
            # unless told tighter; +reserved null/trash blocks.
            # max_entries=capacity keeps the storable length identical to
            # the dense slabs even when capacity % block_size != 0.
            bs = self._block_size
            M = (self._max_blocks_per_seq
                 if self._max_blocks_per_seq is not None
                 else -(-self.capacity // bs))
            nblocks = (self._num_blocks if self._num_blocks is not None
                       else self.num_slots * M + blockpool.NUM_RESERVED)
            self.pool = blockpool.BlockPool(nblocks, bs, self.num_slots, M,
                                            max_entries=self.capacity,
                                            registry=self.obs.registry)
            self.caches = blockpool.init_paged_cache(self.cfg, nblocks, bs,
                                                     kv_dtype=self.kv_dtype)
            self._decode = step(
                "paged_decode",
                rt.make_paged_decode_step(attn_impl=self._attn_impl,
                                          kv_dtype=self.kv_dtype),
                **donate_kw)
            self._splice = jax.jit(_install_admitted_paged, **splice_kw)
            self._copy = jax.jit(blockpool.copy_blocks, **splice_kw)
            if self.scheduler:
                self._mixed = step(
                    "paged_mixed",
                    rt.make_paged_mixed_step(attn_impl=self._attn_impl,
                                             kv_dtype=self.kv_dtype),
                    **donate_kw)
        else:
            self.pool = None
            self.caches = kvcache.init_cache(self.cfg, self.num_slots,
                                             self.capacity)
            self._decode = step(
                "decode",
                rt.make_decode_step(attn_impl=self._attn_impl,
                                    advance_pos=True),
                **donate_kw)
            self._splice = jax.jit(_install_admitted, **splice_kw)
            if self.scheduler:
                self._mixed = step(
                    "mixed", rt.make_mixed_step(attn_impl=self._attn_impl),
                    **donate_kw)
        # footprint gauges: allocation-static per build (the pool is sized
        # up front), so one sync here covers the engine's lifetime
        self._g_kv_bytes.set(self.kv_cache_bytes())
        self._g_kv_f32_bytes.set(self.kv_cache_f32_equiv_bytes())
        # slot state: host-side bookkeeping + device-resident hot-loop state
        self.slot_req: list[Optional[Request]] = [None] * self.num_slots
        # Diagnostic host mirror of per-request progress (next absolute pos,
        # 0 when free).  The hot loop never reads it — the authoritative
        # position array is the device-resident ``_pos``, which also keeps
        # advancing on inactive slots (harmless junk, reset at re-admission).
        self.slot_pos = np.zeros(self.num_slots, np.int32)
        self._tok = jnp.zeros((self.num_slots, 1), jnp.int32)  # last emitted
        self._pos = jnp.zeros((self.num_slots,), jnp.int32)
        self._inflight = None   # (tokens of step t-1, slot->req snap,
        #                          chunk-final (c_next, req, slot) | None,
        #                          device token checksum | None)
        # integrity: region seals {block|slot: (count, fp, alloc gen)},
        # COW copies since the last scrub (corruption propagates through a
        # block copy, so a bad source condemns its descendants), and the
        # dense slots' admission generation (the paged pool tracks its own)
        self._sealed: dict = {}
        self._cow_since_scrub: list = []
        self._slot_gen = np.zeros(self.num_slots, np.int64)
        if self.paged:
            clear_kw = dict(donate_argnums=(0,)) if self._donate else {}
            self._clear = jax.jit(ft_integrity.clear_regions, **clear_kw)
        # scheduler state: the one prompt mid-chunked-prefill (req, slot,
        # consumed token count, paged per-column dst) and this tick's
        # planned chunk
        self._prefilling: Optional[dict] = None
        self._chunk: Optional[dict] = None
        if self.scheduler:
            # park every (free) slot: see _park_pos
            self._pos = jnp.full((self.num_slots,), PAD_POS, jnp.int32)
            seed_kw = dict(donate_argnums=(1, 2)) if self._donate else {}
            self._seed = jax.jit(_seed_hot_loop, **seed_kw)
            park_kw = dict(donate_argnums=(0,)) if self._donate else {}
            self._park = jax.jit(_park_pos, **park_kw)
        # the first dispatch after a (re)build is a compile tick — orders
        # of magnitude above steady state; feeding it to the straggler
        # monitor would poison the small warmup window's median (scheduler
        # engines compile two programs: mixed and decode-only)
        self._straggler_skip = 2 if self.scheduler else 1

    # -- admission ----------------------------------------------------------

    def _paged_reserve(self, req: Request) -> int:
        """Worst-case block-chain length for ``req``: prompt + remaining
        generation budget (capped at the table width — writes past it junk
        to trash, matching the dense engine's out-of-bounds scatter drop).
        ``folded`` tokens already live inside the prompt of a replayed
        request, so they are not counted twice."""
        return min(self.pool.blocks_needed(len(req.prompt)
                                           + req.max_new_tokens
                                           - req.folded),
                   self.pool.max_blocks_per_seq)

    def submit(self, req: Request):
        if self.paged:
            # fail fast on requests the pool can never hold — otherwise
            # admission would hold them back forever, waiting for an
            # eviction that cannot free enough
            nbp = self.pool.blocks_needed(len(req.prompt))
            usable = self.pool.num_blocks - blockpool.NUM_RESERVED
            if (nbp > self.pool.max_blocks_per_seq
                    or self._paged_reserve(req) > usable):
                raise ValueError(
                    f"request rid={req.rid} needs {self._paged_reserve(req)} "
                    f"KV blocks worst-case (prompt alone {nbp}) but the "
                    f"pool has {usable} usable blocks and tables hold "
                    f"{self.pool.max_blocks_per_seq}; grow num_blocks / "
                    f"max_blocks_per_seq or shrink the request")
        req.submitted_at = time.perf_counter()
        if self.scheduler:
            self.sched.enqueue(req)
        else:
            self.queue.append(req)

    def _decoding(self, s: int) -> bool:
        """Slot ``s`` participates in the decode tick: occupied and not the
        slot currently receiving prefill chunks (scheduler mode reserves
        the slot at prefill start; monolithic engines never prefill in
        place, so this reduces to occupancy)."""
        return self.slot_req[s] is not None and (
            self._prefilling is None or self._prefilling["slot"] != s)

    def _backlog(self) -> int:
        """Requests not yet decoding: queued (either admission path) plus
        the one mid-chunked-prefill."""
        n = len(self.queue)
        if self.scheduler:
            n += self.sched.pending + (self._prefilling is not None)
        return n

    def _bucket_len(self, n: int) -> int:
        """Prefill padding bucket for a prompt of length ``n``.

        Dense archs: next power of two (>= 8), capped at capacity so the
        decode-cache tail-trim never drops real entries.  SWA archs (the
        registry's ``caps.swa`` flag): exact length (padding past the window
        would push real KV out of the ring)."""
        if self.caps.swa or n > self.capacity:
            return n
        b = 8
        while b < n:
            b *= 2
        return min(b, self.capacity)

    def _admit_batch(self) -> int:
        """Admit same-bucket queued requests through one padded batched
        prefill call per group.  The group is gathered from a *bounded
        window* at the head of the queue (``admit_window`` entries), so one
        odd-length prompt in the stream no longer splits an otherwise
        batchable admission into multiple prefill calls; the head request
        always leads its group, and the window bound keeps it from being
        starved by later look-alikes.

        Order invariant: submission order is preserved *within a priority
        class*.  A candidate joins the head's group only if it shares the
        head's bucket AND class (grouping across classes would let a
        late-submitted request of another class ride ahead of its own
        class's earlier entries), and the scan keeps a deferral barrier —
        the first same-class same-bucket candidate that cannot join
        (group already full, or — paged — its worst-case block reservation
        no longer fits the pool) ends the scan, so a deferred request can
        never be leapfrogged by a look-alike submitted after it.  The
        paged fit gate (worst-case chains against the unreserved pool, so
        decode-time lazy growth can never exhaust it mid-tick; the check
        is conservative, ignoring prefix sharing) is part of the same scan
        for exactly this reason: trimming after the fact would have to
        re-derive which deferral came first.  Returns number admitted."""
        admitted = 0
        free = [s for s in range(self.num_slots)
                if self.slot_req[s] is None]
        while free and self.queue:
            k = min(len(free), self.max_admit)
            head = self.queue[0]
            blen = self._bucket_len(len(head.prompt))
            avail = self.pool.available_blocks if self.paged else 0
            need, idxs = 0, []
            for i in range(min(len(self.queue), self.admit_window)):
                r = self.queue[i]
                if i and (r.priority != head.priority
                          or self._bucket_len(len(r.prompt)) != blen):
                    continue        # different group: no ordering relation
                if len(idxs) >= k:
                    break           # barrier: group full
                if self.paged:
                    nb = self._paged_reserve(r)
                    if need + nb > avail:
                        break       # barrier: pool can't fit this one yet
                    need += nb
                idxs.append(i)
            if not idxs:            # head doesn't fit: wait for evictions
                break
            group = [self.queue[i] for i in idxs]
            for i in reversed(idxs):
                del self.queue[i]
            slots, free = free[:len(group)], free[len(group):]
            self._admit_group(slots, group, blen)
            admitted += len(group)
        return admitted

    def _admit_group(self, slots: list, group: list, blen: int):
        """One prefill call for ``group`` (same bucket), spliced into
        ``slots``.  The batch is padded to a power-of-two row count by
        repeating the last request (bounded recompilation); pad rows write
        the same payload to the same slot."""
        now = time.perf_counter()
        for s, r in zip(slots, group):
            r.admitted_at = now          # queue exit: prefill starts here
            self.tracer.record("req:queued", r.submitted_at, now, id=r.rid,
                               slot=s)
        with self.tracer.span("admit:prefill", tick=self._tick_no):
            next_tok = self._prefill_and_splice(slots, group, blen)
        with self.tracer.span("admit:wait", tick=self._tick_no):
            first = np.asarray(jax.device_get(next_tok)).reshape(-1)
        now = time.perf_counter()
        for i, (s, r) in enumerate(zip(slots, group)):
            self.slot_req[s] = r
            self.slot_pos[s] = len(r.prompt)
            self._slot_gen[s] += 1    # fresh occupant: stale seals invalid
            tok = int(first[i])
            r.generated.append(tok)
            r.first_token_at = now
            self.stats.admitted += 1
            self.tracer.instant("req:admit", rid=r.rid, slot=s)
            if len(r.generated) >= r.max_new_tokens or tok == r.eos_id:
                self._free(s)     # degenerate: done at prefill

    def _prefill_and_splice(self, slots: list, group: list, blen: int):
        """Build the padded batch, dispatch its prefill and the splice of
        its caches into ``slots``; returns the prefill's first tokens (on
        the device)."""
        B = len(group)
        Bp = 1 << (B - 1).bit_length()
        toks = np.zeros((Bp, blen), np.int32)
        lens = np.zeros(Bp, np.int32)
        slot_ids = np.zeros(Bp, np.int32)
        for i, (s, r) in enumerate(zip(slots, group)):
            L = len(r.prompt)
            toks[i, :L] = r.prompt
            lens[i], slot_ids[i] = L, s
        toks[B:] = toks[B - 1]
        lens[B:], slot_ids[B:] = lens[B - 1], slot_ids[B - 1]

        batch = {"tokens": jnp.asarray(toks), "lengths": jnp.asarray(lens)}
        next_tok, pc = self._prefill(self.params, batch)
        self.stats.prefill_calls += 1
        if self.paged:
            # allocate each row's block chain (full prompt blocks are
            # content-hashed -> shared rows splice to TRASH, skipping the
            # write) and scatter the capacity-padded prefill caches into
            # the first ceil(blen / bs) block columns
            nb = -(-blen // self.pool.block_size)
            dst = np.full((Bp, nb), blockpool.TRASH_BLOCK, np.int32)
            for i, (s, r) in enumerate(zip(slots, group)):
                dst[i] = self.pool.admit(s, r.prompt, nb,
                                         reserve_blocks=self._paged_reserve(r))
            self.caches, self._tok, self._pos = self._splice(
                self.caches, pc, jnp.asarray(dst), jnp.asarray(slot_ids),
                self._tok, self._pos, next_tok, jnp.asarray(lens))
        else:
            self.caches, self._tok, self._pos = self._splice(
                self.caches, pc, jnp.asarray(slot_ids), self._tok, self._pos,
                next_tok, jnp.asarray(lens))
        return next_tok

    def _free(self, slot: int):
        req = self.slot_req[slot]
        req.done = True
        req.finished_at = time.perf_counter()
        self.finished.append(req)
        self.slot_req[slot] = None
        self.slot_pos[slot] = 0
        self.stats.finished += 1
        self.tracer.instant("req:finish", rid=req.rid, slot=slot,
                            tokens=len(req.generated))
        if self.paged:
            self.pool.release(slot)
        if self.scheduler:
            self._pos = self._park(self._pos, slot)
            self.sched.forget(req.rid)

    # -- main loop ----------------------------------------------------------

    def _collect(self, inflight):
        """Pull the previous tick's tokens to the host and apply them.

        Runs *after* the current step was dispatched, so the transfer
        overlaps device compute.  Tokens of slots whose request already
        finished (freed last tick, step was speculative) are discarded.
        A scheduler tick that completed a prompt's final chunk also
        carries that request's first token (``chunk_final``), collected
        with the same one-tick lag as decode tokens.

        With the integrity layer armed the payload carries a
        device-computed checksum; the host copy is re-checksummed after
        the transfer (this is also where scripted ``target=collective``
        corruption flips a bit — in the *host copy*, modeling a corrupt
        device->host hop) and a mismatch re-fetches from the still-
        resident device array, so a corrupted payload is never applied."""
        tok_dev, reqs, chunk_final, tok_sum = inflight
        with self.tracer.span("collect:wait", tick=self._tick_no):
            vals = np.asarray(jax.device_get(tok_dev)).reshape(-1)
        if tok_sum is not None:
            vals = self._verify_payload(tok_dev, vals, tok_sum)
        now = time.perf_counter()
        for slot, req in enumerate(reqs):
            if req is None or req.done:
                continue
            tok = int(vals[slot])
            req.generated.append(tok)
            req.token_times.append(now)
            self.slot_pos[slot] += 1
            self.stats.tokens_out += 1
            if len(req.generated) >= req.max_new_tokens or tok == req.eos_id:
                self._free(slot)
        if chunk_final is not None:
            c_dev, req, slot = chunk_final
            if not req.done:
                tok = int(np.asarray(jax.device_get(c_dev)).reshape(-1)[0])
                req.generated.append(tok)
                req.first_token_at = now
                self.stats.admitted += 1
                self.tracer.instant("req:admit", rid=req.rid, slot=slot)
                if (len(req.generated) >= req.max_new_tokens
                        or tok == req.eos_id):
                    self._free(slot)      # degenerate: done at prefill

    def _dispatch(self):
        """One jitted step over the current slots; returns the
        (device tokens, slot->request snapshot, chunk-final) triple the
        next tick's collection consumes.

        Scheduler mode: when ``_plan_chunk`` scheduled a chunk this tick
        the step is the *mixed* program (decode over every slot + the
        chunk appended into its slot's cache), otherwise the plain decode
        program — exactly two executables, both static-shaped.  Chunk
        progress (``consumed``) only advances here, after a successful
        dispatch, so a retried tick re-dispatches the identical chunk.
        The slot snapshot masks the prefilling slot: its decode lane is
        parked junk, not stream output."""
        ch = self._chunk
        # snapshot the decoding mask before any final-chunk state change:
        # this tick's decode output for the chunk slot is still junk
        reqs = [self.slot_req[s] if self._decoding(s) else None
                for s in range(self.num_slots)]
        c_next = None
        if self.paged:
            # per-tick write plan: lazy chain growth at block
            # boundaries, copy-on-write for shared tails, trash for
            # inactive slots (their junk writes stay unobservable)
            bids = np.empty(self.num_slots, np.int32)
            copies = []
            dequant_blocks = 0
            for s in range(self.num_slots):
                active = self._decoding(s)
                bids[s], cp = self.pool.write_plan(s, active)
                copies.extend(cp)
                if active:
                    dequant_blocks += int(self.pool.seq_blocks[s])
            if self.quantized and dequant_blocks:
                # every active slot's chain is streamed through the
                # in-loop dequant this tick
                self._c_dequant.inc(dequant_blocks)
            if self.scrub_every:
                # corruption propagates through a block copy: the scrub
                # condemns a bad source's descendants along this log
                self._cow_since_scrub.extend(copies)
            if copies:
                # pad to a fixed width (<= 1 COW per slot per tick)
                # with trash self-copies so the jitted copy compiles
                # exactly once
                copies += [(blockpool.TRASH_BLOCK,
                            blockpool.TRASH_BLOCK)] * \
                    (self.num_slots - len(copies))
                self.caches = self._copy(
                    self.caches,
                    jnp.asarray([c[0] for c in copies], jnp.int32),
                    jnp.asarray([c[1] for c in copies], jnp.int32))
            if ch is not None:
                tok, caches, pos, c_next = self._mixed(
                    self.params, self._tok, self.caches, self._pos,
                    jnp.asarray(self.pool.table), jnp.asarray(bids),
                    jnp.asarray(ch["tok"]), jnp.asarray(ch["pos"]),
                    jnp.asarray(ch["table"]), jnp.asarray(ch["bids"]),
                    jnp.asarray([ch["last"]], jnp.int32))
            else:
                tok, caches, pos = self._decode(
                    self.params, self._tok, self.caches, self._pos,
                    jnp.asarray(self.pool.table), jnp.asarray(bids))
        else:
            if ch is not None:
                tok, caches, pos, c_next = self._mixed(
                    self.params, self._tok, self.caches, self._pos,
                    jnp.asarray(ch["tok"]), jnp.asarray(ch["pos"]),
                    jnp.asarray([ch["slot"]], jnp.int32),
                    jnp.asarray([ch["reset"]]),
                    jnp.asarray([ch["last"]], jnp.int32))
            else:
                tok, caches, pos = self._decode(self.params, self._tok,
                                                self.caches, self._pos)
        # the old cache buffer was donated — replace references now
        self.caches, self._tok, self._pos = caches, tok, pos
        self.stats.ticks += 1
        chunk_final = None
        if ch is not None:
            self.stats.chunk_ticks += 1
            pf = self._prefilling
            pf["consumed"] = ch["start"] + ch["n"]
            if ch["final"]:
                req, slot = ch["req"], ch["slot"]
                L = len(req.prompt)
                # seed the hot loop: the chunk's sampled next token at
                # position L — the slot starts decoding next tick
                self._tok, self._pos = self._seed(
                    jnp.asarray([slot], jnp.int32), self._tok, self._pos,
                    c_next, jnp.asarray([L], jnp.int32))
                self.slot_pos[slot] = L
                self._prefilling = None
                chunk_final = (c_next, req, slot)
        # NB: return self._tok, not tok — the final-chunk seeding above
        # donated tok's buffer; the seeded array is lane-identical for
        # every decoding slot (the chunk slot is masked out of reqs)
        tok_sum = (ft_integrity.leaf_fingerprint_jit(self._tok)
                   if self.scrub_every else None)
        return (self._tok, reqs, chunk_final, tok_sum)

    def _plan_chunk(self) -> Optional[dict]:
        """Scheduler-mode host planning for this tick's prefill chunk.

        Starts the next waiting prompt when none is in flight (scheduler
        ``select()``: WRR across priority classes + starvation aging) and
        a slot is free — paged engines allocate the request's full block
        chain here (``pool.admit``: prefix-shared blocks resolve now, the
        worst-case reservation gates like monolithic admission).  Then
        shapes this tick's chunk under the token budget
        (``sched.chunk_tokens``); a saturated tick returns None
        (decode-only).  All pure host bookkeeping — chunk *progress*
        advances in ``_dispatch``, after the step actually ran."""
        if self._prefilling is None and self.sched.pending:
            free = next((s for s in range(self.num_slots)
                         if self.slot_req[s] is None), None)
            if free is not None:
                req = self.sched.select()
                if self.paged and \
                        self._paged_reserve(req) > self.pool.available_blocks:
                    # pool can't hold it yet: put it back at the front of
                    # its class (order preserved) and wait for evictions
                    self.sched.requeue_front([req])
                else:
                    req.admitted_at = time.perf_counter()
                    self.tracer.record("req:queued", req.submitted_at,
                                       req.admitted_at, id=req.rid, slot=free)
                    self.slot_req[free] = req
                    self.slot_pos[free] = 0
                    self._slot_gen[free] += 1
                    dst = None
                    if self.paged:
                        nb = self.pool.blocks_needed(len(req.prompt))
                        dst = self.pool.admit(
                            free, req.prompt, nb,
                            reserve_blocks=self._paged_reserve(req))
                    self._prefilling = {"req": req, "slot": free,
                                        "consumed": 0, "dst": dst}
        pf = self._prefilling
        if pf is None:
            return None
        req, slot = pf["req"], pf["slot"]
        L = len(req.prompt)
        active = sum(self._decoding(s) for s in range(self.num_slots))
        n = self.sched.chunk_tokens(active, L - pf["consumed"])
        if n == 0:
            return None             # budget saturated: decode-only tick
        start = pf["consumed"]
        C = self.sched.chunk_size
        c_tok = np.zeros((1, C), np.int32)
        c_pos = np.full((1, C), PAD_POS, np.int32)
        c_tok[0, :n] = req.prompt[start:start + n]
        c_pos[0, :n] = np.arange(start, start + n, dtype=np.int32)
        chunk = {"req": req, "slot": slot, "start": start, "n": n,
                 "tok": c_tok, "pos": c_pos, "reset": start == 0,
                 "last": n - 1, "final": start + n >= L}
        if self.paged:
            bs = self.pool.block_size
            dst = pf["dst"]
            bids = np.full((1, C), blockpool.TRASH_BLOCK, np.int32)
            for j in range(n):
                # per-token destination: the admitted chain's column —
                # TRASH for prefix-shared columns (already written by
                # their first owner) and for pads
                bids[0, j] = dst[(start + j) // bs]
            chunk["bids"] = bids
            chunk["table"] = np.asarray(self.pool.table[slot:slot + 1],
                                        np.int32)
        return chunk

    def _dispatch_with_retry(self, t: int):
        """Dispatch with bounded retry-with-backoff: a transient tick
        failure is retried up to ``tick_retries`` times before escalating
        to evacuation.  Scripted faults fire via ``injector.on_tick``
        *before* the jitted step, so a failed attempt never half-consumes
        the donated cache buffers (the paged write plan likewise only
        advances inside a successful ``_dispatch``).  A compile refusal
        (:class:`StepCompileError`) is a program error and propagates at
        once."""
        last = None
        for attempt in range(self.tick_retries + 1):
            try:
                if self.injector is not None:
                    self.injector.on_tick(t)
                return self._dispatch()
            except StepCompileError:
                raise
            except Exception as e:  # noqa: BLE001 — retry, then escalate
                last = e
                self.stats.tick_retries += 1
                self._log_event("tick_retry", tick=t, attempt=attempt,
                                error=repr(e))
                time.sleep(self.retry_backoff_s * (2 ** attempt))
        self._evacuate(tick=t,
                       reason=(f"tick failed {self.tick_retries + 1} "
                               f"attempts: {last!r}"),
                       bad=self._suspects())
        return None

    def tick(self) -> bool:
        """Dispatch one step, collect the previous one, admit.

        Order matters: dispatch first (device starts immediately), then the
        host overlaps collection + admission bookkeeping with the running
        step.  Monolithic admissions take effect on the next tick's step
        (the splice is queued behind the step via its data dependency on
        the caches); scheduler mode instead *plans* a prefill chunk before
        dispatch and rides it inside the mixed step, so admission is the
        decode tick — no stream ever waits for a whole prompt.

        Fault tolerance wraps the loop: on the ``health_every`` cadence the
        tick first consults ``ft.health.check_devices`` (with scripted
        faults overlaid), the dispatch is retried with backoff on transient
        failures, and the tick wall time feeds the ``StragglerMonitor``
        (which escalates only when armed with ``straggler_kw``); every
        escalation converges on :meth:`_evacuate`.

        Observability wraps it once more: the whole tick is a ``tick``
        span (a profiler step) with ``plan`` / ``dispatch`` / ``collect``
        / ``admit`` (and ``health`` / ``scrub``) child spans — strictly
        nested, never crossing a tick boundary.  ``collect:wait`` is the
        blocking read of the step's tokens inside ``collect``; ``admit``
        (opened only while requests wait) holds ``admit:prefill`` (batch,
        prefill and splice dispatch) and ``admit:wait`` (the read of the
        first tokens) per admitted group, and each admitted request's
        queue wait is recorded as a ``req:queued`` span under its rid.
        The queue/active-slot gauges are refreshed at tick exit.  With the
        tracer disabled (the default) every span is the shared no-op
        context manager, which is the near-zero-overhead contract
        bench_serve asserts."""
        self._tick_no += 1
        t = self._tick_no
        with self.tracer.step("tick", t, tick=t):
            busy = self._tick_body(t)
        self._g_queue.set(self._backlog())
        self._g_active.set(sum(self._decoding(s)
                               for s in range(self.num_slots)))
        return busy

    def _tick_body(self, t: int) -> bool:
        if self.health_every and t % self.health_every == 0:
            with self.tracer.span("health", tick=t):
                self._health_gate(t)
        if self.scrub_every and self.injector is not None:
            # scripted silent corruption lands *before* dispatch: this
            # tick's step reads the flipped bits, and the scrub below must
            # catch them before its output is ever collected
            self._apply_corruptions(t)

        self._chunk = None
        if self.scheduler:
            with self.tracer.span("plan", tick=t):
                self.sched.on_tick()
                self._chunk = self._plan_chunk()

        t_start = time.perf_counter()
        dispatched = None
        if self._chunk is not None or \
                any(self._decoding(s) for s in range(self.num_slots)):
            with self.tracer.span("dispatch", tick=t):
                dispatched = self._dispatch_with_retry(t)

        processed = self._inflight is not None
        if processed:
            with self.tracer.span("collect", tick=t):
                self._collect(self._inflight)
        self._inflight = dispatched

        if dispatched is not None:
            if self._straggler_skip:
                self._straggler_skip -= 1       # compile tick: not baseline
            else:
                # the tick critical path (dispatch + overlapped collection)
                rep = self.straggler.observe(t,
                                             time.perf_counter() - t_start)
                if rep.action != "ok" and self._straggler_escalates:
                    self._on_straggler(t, rep)

        if self.scrub_every and t % self.scrub_every == 0:
            # after the inflight swap: a detection can still drop the
            # just-dispatched (corrupt) lane before it is ever collected
            with self.tracer.span("scrub", tick=t):
                self._scrub(t)

        if self.scheduler:
            return (dispatched is not None or processed
                    or self._backlog() > 0)
        admitted = 0
        if self.queue:
            with self.tracer.span("admit", tick=t):
                admitted = self._admit_batch()
        return dispatched is not None or processed or admitted > 0

    # -- fault handling -------------------------------------------------------

    def _log_event(self, kind: str, **fields):
        self.ft_events.append({"event": kind, **fields})
        self._c_events.labels(event=kind).inc()
        self.tracer.instant("ft:" + kind, **fields)

    def _suspects(self) -> set:
        """Device ids implicated by fired scripted faults — the only
        attribution source for raise/stall failures (a real deployment
        would read XLA error payloads here)."""
        return (self.injector.suspect_devices()
                if self.injector is not None else set())

    def _health_gate(self, t: int):
        """Proof-of-work health check over the engine's devices, scripted
        faults overlaid; any unhealthy device escalates straight to
        evacuation (a failed checksum is not a transient).  With the
        integrity layer armed the gate also re-verifies the params
        checksum registered at build — a mismatch is silent data
        corruption (``HealthReason.DATA_CORRUPTION``), recovered by a
        params restore + full stream rollback, not an evacuation (the
        devices are fine; the bits are not)."""
        if self._params_fp is not None and not self._verify_params():
            self._log_event(
                "health", tick=t,
                failed=[{"device": "params",
                         "reason": ft_health.HealthReason
                         .DATA_CORRUPTION.value,
                         "detail": "params fingerprint mismatch"}])
            self._recover_params(t, origin="health_gate")
        t0 = time.perf_counter()
        reports = ft_health.check_devices(self._devices)
        if self.injector is not None:
            reports = self.injector.apply_health(reports, self._devices, t)
        self._h_health.observe(time.perf_counter() - t0)
        self.stats.health_checks += 1
        bad = [(r, d) for r, d in zip(reports, self._devices) if not r.ok]
        if not bad:
            return
        self._log_event(
            "health", tick=t,
            failed=[{"device": r.device, "reason": r.reason.value,
                     "detail": r.detail} for r, _ in bad])
        self._evacuate(
            tick=t,
            reason="unhealthy devices: " + ", ".join(
                f"{r.device}[{r.reason.value}]" for r, _ in bad),
            bad={d.id for _, d in bad})

    def _on_straggler(self, t: int, rep):
        self._log_event("straggler", tick=t, action=rep.action,
                        ratio=round(rep.ratio, 2),
                        step_time=round(rep.step_time, 5),
                        median=round(rep.median, 5))
        if rep.action in ("remesh", "abort"):
            self._evacuate(
                tick=t,
                reason=f"straggler {rep.action} "
                       f"(tick {rep.ratio:.1f}x rolling median)",
                bad=self._suspects())

    # -- data integrity -------------------------------------------------------

    def _register_params_integrity(self):
        """Register the params checksum + host restore source.  The backup
        stands in for the last checkpoint (``EngineSnapshot`` deliberately
        excludes weights); a deployment would reload from
        ``checkpoint.load_pytree`` instead, through the same path."""
        self._params_fp = int(jax.device_get(
            ft_integrity.tree_fingerprint_jit(self.params)))
        self._params_backup = jax.device_get(self.params)

    def _verify_params(self) -> bool:
        return self._params_fp == int(jax.device_get(
            ft_integrity.tree_fingerprint_jit(self.params)))

    def _verify_payload(self, tok_dev, vals: np.ndarray,
                        tok_sum) -> np.ndarray:
        """Checksum-verify the device->host token transfer.  Scripted
        ``target=collective`` faults flip a bit in the *host copy* here
        (the transfer is the corruption point); a mismatch re-fetches from
        the still-resident device array, so a corrupted payload is never
        applied to any stream."""
        t = self._tick_no
        if self.injector is not None:
            for f in self.injector.due_corruptions(t, "collective"):
                f.fired += 1
                rng = np.random.default_rng((0x7A6, f.seed, f.fired))
                i = int(rng.integers(vals.size))
                b = int(rng.integers(32))
                vals = vals.copy()
                vals[i] = np.int32(np.uint32(vals[i]) ^ np.uint32(1 << b))
                self._last_inject["collective"] = t
                self._log_event("corrupt_inject", tick=t,
                                target="collective", index=i, bit=b)
        expect = int(jax.device_get(tok_sum))
        if ft_integrity.host_leaf_fingerprint(vals) == expect:
            return vals
        self.stats.corruption_detected += 1
        self.stats.transfer_retries += 1
        lat = t - self._last_inject.get("collective", t)
        self._h_detect.observe(lat)
        self._log_event("corruption", tick=t, target="collective",
                        detect_latency_ticks=lat)
        fresh = np.asarray(jax.device_get(tok_dev)).reshape(-1)
        if ft_integrity.host_leaf_fingerprint(fresh) != expect:
            raise RuntimeError(
                "token payload checksum mismatch persists after re-fetch: "
                "the device-resident payload itself is corrupt")
        return fresh

    def _apply_corruptions(self, t: int):
        """Fire due scripted ``kind=corrupt`` faults (kv and params
        targets) before dispatch; ``target=collective`` fires at
        collection (:meth:`_verify_payload`).  A kv fault with nothing
        sealed yet stays armed — a real upset by definition hits resident
        data."""
        for f in self.injector.due_corruptions(t, "kv"):
            if self._corrupt_kv(t, f):
                f.fired += 1
        for f in self.injector.due_corruptions(t, "params"):
            f.fired += 1
            self._corrupt_params(t, f)

    def _corrupt_kv(self, t: int, f) -> bool:
        """Flip one seeded bit inside a currently *sealed* span (the
        detection-guaranteed region: decode only ever appends past a
        seal, so the flip can never be legitimately overwritten before
        the next scrub)."""
        cand = []
        for r, (cnt, fp, gen) in sorted(self._sealed.items()):
            cur = (self.pool.alloc_gen[r] if self.paged
                   else self._slot_gen[r])
            if cnt > 0 and gen == int(cur):
                cand.append((r, cnt))
        if not cand:
            return False
        rng = np.random.default_rng((0xC0, f.seed, f.fired))
        r, cnt = cand[int(rng.integers(len(cand)))]
        leaves, treedef = jax.tree_util.tree_flatten(self.caches)
        j = int(rng.integers(len(leaves)))
        leaf = leaves[j]
        shape = leaf.shape                     # [R, region, entry, ...]
        # entry axis is the block offset for payload/pos leaves but the
        # kv-head for the int8 pool's [R, N, KV] scale leaves — bound the
        # coordinate by both so the flip stays inside the sealed span
        mi = (int(rng.integers(shape[0])), r,
              int(rng.integers(min(cnt, shape[2]))),
              *(int(rng.integers(d)) for d in shape[3:]))
        flat = int(np.ravel_multi_index(mi, shape))
        bit = int(rng.integers(ft_integrity.bit_width(leaf.dtype)))
        leaves[j] = ft_integrity.flip_bit_jit(leaf, flat, bit)
        self.caches = jax.tree_util.tree_unflatten(treedef, leaves)
        self._last_inject["kv"] = t
        self._log_event("corrupt_inject", tick=t, target="kv",
                        region=int(r), leaf=j, bit=bit)
        return True

    def _corrupt_params(self, t: int, f):
        leaves, treedef = jax.tree_util.tree_flatten(self.params)
        rng = np.random.default_rng((0xBAD, f.seed, f.fired))
        j = int(rng.integers(len(leaves)))
        leaf = leaves[j]
        flat = int(rng.integers(leaf.size))
        bit = int(rng.integers(ft_integrity.bit_width(leaf.dtype)))
        leaves[j] = ft_integrity.flip_bit_jit(leaf, flat, bit)
        self.params = jax.tree_util.tree_unflatten(treedef, leaves)
        self._last_inject["params"] = t
        self._log_event("corrupt_inject", tick=t, target="params",
                        leaf=j, bit=bit)

    def _scrub(self, t: int):
        """Integrity scrub: wipe + release blocks quarantined last round,
        re-verify every seal at its recorded extent, recover from
        anything that fails, then reseal the current state and advance
        the per-request ``verified`` watermarks."""
        self.stats.scrubs += 1
        if self.paged:
            ready = self.pool.scrub_poisoned()
            if ready:
                self.caches = self._clear(
                    self.caches, jnp.asarray(ready, jnp.int32))
                self._log_event("scrub_clean", tick=t,
                                blocks=[int(b) for b in ready])
        bad = self._verify_seals()
        if bad:
            self._recover_kv(t, bad)
        if self._params_fp is not None and not self._verify_params():
            self._recover_params(t, origin="scrub")
        self._reseal()
        self._cow_since_scrub = []

    def _verify_seals(self) -> list:
        """Regions whose recorded fingerprint no longer matches.  Seals
        whose region was legitimately recycled since (allocation
        generation moved) are skipped — recycling rewrites bits by
        design."""
        if not self._sealed:
            return []
        N = self.pool.num_blocks if self.paged else self.num_slots
        counts = np.zeros(N, np.int32)
        valid = {}
        for r, (cnt, fp, gen) in self._sealed.items():
            cur = (self.pool.alloc_gen[r] if self.paged
                   else self._slot_gen[r])
            if cnt > 0 and gen == int(cur):
                counts[r] = cnt
                valid[r] = fp
        if not valid:
            return []
        fps = np.asarray(jax.device_get(
            ft_integrity.region_fingerprints_jit(
                self.caches, jnp.asarray(counts))))
        return sorted(r for r, fp in valid.items() if int(fps[r]) != fp)

    def _reseal(self):
        """Fingerprint the written span of every tracked region — pool
        blocks along live chains (shared blocks at their fullest view)
        plus registered cached-free blocks (a future prompt may share
        them), or dense occupied slot rows up to the collected watermark
        — in one jitted masked reduction."""
        counts: dict = {}
        pf = self._prefilling
        if self.paged:
            pool, bs = self.pool, self.pool.block_size
            for s in range(self.num_slots):
                nb = int(pool.seq_blocks[s])
                if nb == 0:
                    continue
                entries = (pf["consumed"]
                           if pf is not None and pf["slot"] == s
                           else int(pool.next_pos[s]))
                for col in range(nb):
                    bid = int(pool.table[s, col])
                    cnt = min(max(entries - col * bs, 0), bs)
                    # int8 pool: a partially-filled block is still
                    # mutable below its write cursor — a later append can
                    # grow the per-(block, kv-head) scale and requantize
                    # the already-written entries in place.  Only a FULL
                    # block's bits are immutable, so only full blocks
                    # seal (the open tail is covered once it fills).
                    if self.quantized and cnt < bs:
                        continue
                    counts[bid] = max(counts.get(bid, 0), cnt)
            for bid in pool._key_of:
                if int(pool.refcount[bid]) == 0:
                    counts[bid] = bs
            N = pool.num_blocks
            gen = pool.alloc_gen
        else:
            for s in range(self.num_slots):
                if self.slot_req[s] is None:
                    continue
                entries = (pf["consumed"]
                           if pf is not None and pf["slot"] == s
                           else int(self.slot_pos[s]))
                counts[s] = min(entries, self.capacity)
            N = self.num_slots
            gen = self._slot_gen
        counts = {r: c for r, c in counts.items() if c > 0}
        if counts:
            vec = np.zeros(N, np.int32)
            for r, c in counts.items():
                vec[r] = c
            fps = np.asarray(jax.device_get(
                ft_integrity.region_fingerprints_jit(
                    self.caches, jnp.asarray(vec))))
            self._sealed = {r: (c, int(fps[r]), int(gen[r]))
                            for r, c in counts.items()}
        else:
            self._sealed = {}
        # clean scrub: every collected token of a live stream came from
        # state now proven intact — advance the rollback watermarks
        for s in range(self.num_slots):
            r = self.slot_req[s]
            if r is not None:
                r.verified = len(r.generated)

    def _recover_kv(self, t: int, bad: list):
        """Quarantine-and-replay for corrupted KV: poison the blocks (and
        their copy-on-write descendants), roll every affected stream back
        to its verified watermark and requeue it through standard prefill
        admission.  Per-stream recovery — no mesh rebuild, unaffected
        streams never notice."""
        self.stats.corruption_detected += len(bad)
        lat = t - self._last_inject.get("kv", t)
        self._h_detect.observe(lat)
        bad = set(bad)
        if self.paged:
            for src, dst in self._cow_since_scrub:
                if src in bad:
                    bad.add(dst)
            affected = [s for s in range(self.num_slots)
                        if int(self.pool.seq_blocks[s])
                        and any(b in bad for b in self.pool.chain(s))]
            for bid in sorted(bad):
                self.pool.poison(bid)
        else:
            affected = sorted(bad)
        self.stats.kv_quarantined += len(bad)
        replayed = self._replay_streams(affected)
        self._log_event(
            "corruption", tick=t, target="kv",
            regions=[int(b) for b in sorted(bad)],
            streams=[r.rid for r in replayed],
            detect_latency_ticks=lat)

    def _recover_params(self, t: int, origin: str):
        """Silent params corruption: restore from the registered backup
        and roll back *every* live stream — KV appended under corrupted
        params is garbage wearing a valid seal, so affected chains are
        quarantined wholesale and the prefix cache is dropped (a replayed
        prompt must not share a garbage block)."""
        self.stats.corruption_detected += 1
        self.stats.params_restores += 1
        # host numpy restore: jit re-places per the executable's shardings
        # on the next dispatch (same path evacuation's host round-trip uses)
        self.params = jax.tree.map(np.asarray, self._params_backup)
        affected = [s for s in range(self.num_slots)
                    if self.slot_req[s] is not None]
        if self.paged:
            bad = set()
            for s in affected:
                bad.update(self.pool.chain(s))
            for bid in sorted(bad):
                self.pool.poison(bid)
            self.pool.drop_prefix_cache()
            self.stats.kv_quarantined += len(bad)
        replayed = self._replay_streams(affected)
        self._sealed = {}       # every seal is suspect under bad params
        lat = t - self._last_inject.get("params", t)
        self._h_detect.observe(lat)
        self._log_event(
            "corruption", tick=t, target="params", origin=origin,
            streams=[r.rid for r in replayed],
            detect_latency_ticks=lat)

    def _replay_streams(self, slots: list) -> list:
        """Roll the given slots' streams back to their verified
        watermarks and requeue them at the queue head: truncate suspect
        tokens, drop the not-yet-collected inflight lane, fold, release
        the slot.  Standard admission then replays prompt+generated
        through prefill — same per-stream contract as evacuation, without
        touching the mesh."""
        replayed = []
        for s in sorted(slots):
            req = self.slot_req[s]
            if req is None:
                continue
            inf = self._inflight
            if inf is not None:
                tok_dev, reqs, chunk_final, tok_sum = inf
                if reqs[s] is req:
                    reqs[s] = None      # suspect lane: never collect it
                if chunk_final is not None and chunk_final[1] is req:
                    self._inflight = (tok_dev, reqs, None, tok_sum)
            keep = max(req.verified, req.folded)
            del req.generated[keep:]
            del req.token_times[max(0, keep - 1):]
            _fold_replay_prefix(req)
            self.slot_req[s] = None
            self.slot_pos[s] = 0
            if self.paged:
                self.pool.release(s)
            if self.scheduler:
                self._pos = self._park(self._pos, s)
            if self._prefilling is not None \
                    and self._prefilling["slot"] == s:
                self._prefilling = None
            replayed.append(req)
        if replayed:
            self.stats.streams_replayed += len(replayed)
            if self.scheduler:
                self.sched.requeue_front(replayed)
            else:
                for r in reversed(replayed):
                    self.queue.appendleft(r)
        return replayed

    def apply_link_reports(self, reports, *, ber_threshold: float = 1e-9):
        """Demote the mesh for links failing the BER threshold — the
        serving end of the PRBS link sweep (core/linktest.py).  A failing
        *data*-parallel axis drops its trailing device slice through the
        standard evacuation path (streams replay, TP preserved); a
        failing model axis cannot shrink below one TP group, so it is
        logged as degraded (fabric derating via
        ``core.fabric.Fabric.with_link_ber`` is the planner's recourse).
        Returns the evicted device ids."""
        if reports:
            # rolling per-axis BER/bandwidth gauges, independent of any
            # eviction decision — the continuous-monitoring half of IBERT
            self.linkmon.record(reports)
        if self.mesh is None:
            return []
        failing = [r for r in reports
                   if (not r.ok) or r.ber > ber_threshold]
        if not failing:
            return []
        names = list(self.mesh.axis_names)
        shape = dict(zip(names, self.mesh.devices.shape))
        victims: set = set()
        for rep in failing:
            ax = getattr(rep, "axis", None)
            if ax not in shape:
                continue
            if ax == "model" or shape[ax] <= 1:
                self._log_event("degraded_link", tick=self._tick_no,
                                axis=ax, ber=rep.ber,
                                threshold=ber_threshold)
                continue
            sl = [slice(None)] * self.mesh.devices.ndim
            sl[names.index(ax)] = slice(shape[ax] - 1, shape[ax])
            victims.update(
                d.id for d in self.mesh.devices[tuple(sl)].flatten())
        if victims:
            self._evacuate(
                tick=self._tick_no,
                reason="link BER over threshold on "
                       + ",".join(sorted(r.axis for r in failing)),
                bad=victims)
        return sorted(victims)

    def _evacuate(self, *, tick: int, reason: str, bad: set):
        """Live evacuation: move every in-flight stream onto a surviving
        mesh without dropping it.

        1. flush the in-flight token transfer (the last healthy tick's
           tokens belong to their streams),
        2. snapshot per-request portable state — tokens emitted, position,
           and (paged) the block chain, the host-side KV identity — and
           fold each stream's generated prefix into its prompt,
        3. pick the surviving mesh: ``ft.elastic.evacuation_mesh`` over
           the non-implicated devices preserves the TP axis (survivors <
           one TP group raises — restore from checkpoint instead); with no
           device attribution the rebuild is in place (a process-level
           fault, same devices),
        4. ``Runtime.reshape()`` onto it — params take a host round-trip
           so the rebuilt executables re-commit them — and rebuild the
           data path,
        5. requeue the snapshot at the queue head: standard admission
           replays each prefix through prefill, so the continued streams
           are the same f32 tokens the uninterrupted run emits.
        """
        if self.stats.evacuations >= self.max_evacuations:
            raise RuntimeError(
                f"giving up after {self.stats.evacuations} evacuations "
                f"(latest trigger: {reason})")
        t0 = time.perf_counter()
        if self._inflight is not None:
            self._collect(self._inflight)
            self._inflight = None
        live, chains = [], {}
        mid_prefill = (self._prefilling["req"].rid
                       if self._prefilling is not None else None)
        for s in range(self.num_slots):
            r = self.slot_req[s]
            if r is None:
                continue
            if self.paged:
                chains[r.rid] = self.pool.chain(s)
            # a mid-prefill request has no unfolded generated tail (its
            # first token only arrives with the final chunk), so folding
            # is a no-op and re-admission replays the prompt exactly once
            _fold_replay_prefix(r)
            live.append(r)
        # drop in-flight chunk state: the interrupted prompt re-enters the
        # queue and restarts its chunk sequence on the rebuilt caches
        self._prefilling = None
        self._chunk = None
        bad = set(bad)
        if self.mesh is not None and bad:
            survivors = [d for d in self._devices if d.id not in bad]
            new_mesh = ft_elastic.evacuation_mesh(
                survivors, tp=self.plan.tp_size,
                prefer_pods=self.plan.mesh_axes.get("pod", 1))
        else:
            new_mesh = self.mesh    # no attribution: rebuild in place
        # params leave the (possibly dead) old placement via the host; the
        # rebuilt executables re-commit them under the new mesh
        self.params = jax.tree.map(jax.device_get, self.params)
        self.rt = self.rt.reshape(mesh=new_mesh)
        self._build_data_path()
        if self.scheduler:
            self.sched.requeue_front(live)
        else:
            for r in reversed(live):
                self.queue.appendleft(r)
        # the new mesh's tick times are a new distribution — don't judge
        # them against the old rolling median
        self.straggler.reset()
        self.stats.evacuations += 1
        dur = time.perf_counter() - t0
        self._h_evac.observe(dur)
        self._log_event(
            "evacuate", tick=tick, reason=reason, requeued=len(live),
            replayed=[r.rid for r in live], mid_prefill=mid_prefill,
            kv_chains=chains or None,
            mesh=(dict(zip(self.mesh.axis_names,
                           self.mesh.devices.shape))
                  if self.mesh is not None else None),
            latency_s=round(dur, 4))

    # -- warm restart ---------------------------------------------------------

    def snapshot(self) -> EngineSnapshot:
        """Warm-restart snapshot: every in-flight (slot order) and queued
        request in replay-ready form.  Flushes the in-flight token
        transfer first — a snapshot must not lose the already-dispatched
        tick — so taking one advances the engine by the tokens it had
        computed; device caches are deliberately NOT captured (restore
        replays prompts through prefill, same contract as evacuation)."""
        if self._inflight is not None:
            self._collect(self._inflight)
            self._inflight = None
        live = [r for r in self.slot_req if r is not None]
        waiting = self.sched.waiting() if self.scheduler else list(self.queue)
        reqs = []
        for r in list(live) + waiting:
            _fold_replay_prefix(r)
            reqs.append({"rid": int(r.rid),
                         "prompt": [int(x) for x in np.asarray(r.prompt)],
                         "generated": [int(x) for x in r.generated],
                         "max_new_tokens": int(r.max_new_tokens),
                         "eos_id": int(r.eos_id),
                         "priority": int(r.priority)})
        return EngineSnapshot(
            requests=reqs,
            stats={k: getattr(self.stats, k)
                   for k in ("ticks", "tokens_out", "admitted", "finished",
                             "prefill_calls", "evacuations", "tick_retries",
                             "health_checks")},
            meta={"arch": self.cfg.name, "kv_layout": self.kv_layout,
                  "kv_dtype": self.kv_dtype,
                  "capacity": self.capacity, "num_slots": self.num_slots,
                  "scheduler": bool(self.scheduler),
                  "tick": self._tick_no})

    def load_snapshot(self, snap: EngineSnapshot) -> int:
        """Warm restart: requeue a snapshot's requests into this idle
        engine; each replays through standard prefill admission and
        continues its stream (``folded`` marks the whole ``generated``
        prefix as already in the prompt).  Returns the request count."""
        if any(r is not None for r in self.slot_req) or self._backlog():
            raise RuntimeError(
                "load_snapshot needs an idle engine (no live slots, empty "
                "queue) — restore into a freshly built engine")
        if snap.meta.get("arch") not in (None, self.cfg.name):
            raise ValueError(
                f"snapshot was taken on arch {snap.meta.get('arch')!r} but "
                f"this engine serves {self.cfg.name!r}")
        for d in snap.requests:
            gen = list(d.get("generated", []))
            self.submit(Request(
                rid=int(d["rid"]),
                prompt=np.asarray(d["prompt"], np.int32),
                max_new_tokens=int(d["max_new_tokens"]),
                eos_id=int(d.get("eos_id", -1)),
                priority=int(d.get("priority", 0)),
                generated=gen, folded=len(gen)))
        return len(snap.requests)

    def run_to_completion(self, max_ticks: int = 10_000) -> EngineStats:
        for _ in range(max_ticks):
            busy = self.tick()
            if not busy and not self._backlog():
                break
        return self.stats

    # -- reporting -----------------------------------------------------------

    def latency_summary(self) -> dict:
        """p50/p95/p99 time-to-first-token, inter-token latency and
        queue-wait (seconds) over finished requests.  TTFT = submit ->
        prefill token; ITL = consecutive decode-token arrivals at
        collection (one tick behind dispatch — the double-buffering
        contract — which is what a client observes); queue wait = submit
        -> prefill start, the share of TTFT spent purely in admission
        (the number the scheduler's fairness knobs move)."""
        ttfts, itls, waits = [], [], []
        for r in self.finished:
            if r.first_token_at:
                ttfts.append(r.first_token_at - r.submitted_at)
            if r.admitted_at:
                waits.append(r.admitted_at - r.submitted_at)
            times = [r.first_token_at] + list(r.token_times)
            itls.extend(b - a for a, b in zip(times, times[1:]))
        out = {"requests": len(ttfts)}
        for name, xs in (("ttft", ttfts), ("itl", itls),
                         ("queue_wait", waits)):
            out.update(latency_fields(name, xs))
        return out

    def kv_cache_bytes(self) -> int:
        """Bytes of attention K/V storage (dense per-slot slabs or the
        paged pool, including any int8 scale pools) — the footprint
        BENCH_serve.json tracks for the dense / paged / paged-int8
        comparison."""
        total = 0
        for gc in self.caches:
            for sub in gc.values():
                for name in ("k", "v", "xk", "xv", "k_scale", "v_scale"):
                    if name in sub:
                        a = sub[name]
                        total += a.size * a.dtype.itemsize
        return total

    def kv_cache_f32_equiv_bytes(self) -> int:
        """Bytes the same K/V entries would occupy at full precision (no
        scale pools) — the denominator behind the quantized-pool footprint
        gauge pair.  Equals :meth:`kv_cache_bytes` for f32 engines."""
        itemsize = jnp.dtype(self.cfg.dtype).itemsize
        total = 0
        for gc in self.caches:
            for sub in gc.values():
                for name in ("k", "v", "xk", "xv"):
                    if name in sub:
                        total += sub[name].size * itemsize
        return total
