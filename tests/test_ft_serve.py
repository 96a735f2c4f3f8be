"""Fault-tolerant serving suite.

The contract under test: a scripted fault (ft/inject.py) mid-serve must
never drop or corrupt a stream — the engine retries transients, evacuates
onto the surviving mesh on anything worse, replays every in-flight prefix
through prefill, and the continued token streams are identical (f32) to a
fault-free run.  Single-device tests exercise the in-place-rebuild
evacuation (no device attribution); the mesh-shrink path (2x4 -> 1x4 after
losing a device) needs the forced 8-device CPU topology
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``; scripts/ci.sh
runs this file as its own gate with that env) and skips elsewhere.

Parity runs in f32 (``cfg.scaled(dtype=jnp.float32)``): pre- and
post-evacuation execute different XLA programs over identical values, so
bf16 would expose argmax decisions to sub-ulp reassociation noise that has
nothing to do with the recovery logic under test.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import EngineSnapshot
from repro.configs import get_smoke_config
from repro.ft.elastic import best_mesh_shape, evacuation_mesh, plan_remesh
from repro.ft.health import DeviceHealth, HealthReason, check_devices
from repro.ft.inject import Fault, FaultInjector, InjectedFault
from repro.ft.straggler import StragglerMonitor
from repro.runtime import Runtime
from repro.serve.engine import Request

needs8 = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8 "
           "(scripts/ci.sh runs this gate)")

ARCH = "llama3.2-3b"


def _cfg():
    return get_smoke_config(ARCH).scaled(dtype=jnp.float32)


def _stream(cfg, n=5, seed=3):
    """Mixed-length requests plus a shared-prefix pair (two full
    block_size=8 blocks) so paged runs exercise prefix reuse."""
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        size=int(rng.integers(3, 14)),
                                        dtype=np.int32),
                    max_new_tokens=int(rng.integers(4, 9)))
            for i in range(n)]
    shared = rng.integers(0, cfg.vocab_size, size=16, dtype=np.int32)
    for rid, tail in ((100, [5, 6]), (101, [7, 8])):
        reqs.append(Request(rid=rid,
                            prompt=np.concatenate([shared, tail]).astype(
                                np.int32),
                            max_new_tokens=4))
    return reqs


def _run(cfg, *, mesh=None, kv_layout="dense", injector=None, **kw):
    rt = Runtime.create(cfg, mesh, shape_kind="decode", capacity=32,
                        kv_layout=kv_layout)
    kw.setdefault("retry_backoff_s", 0.001)
    eng = rt.engine(num_slots=2, injector=injector, **kw)
    for r in _stream(cfg):
        eng.submit(r)
    eng.run_to_completion()
    assert len(eng.finished) == 7, "stream dropped"
    return eng


def _tokens(eng):
    return {r.rid: list(r.generated) for r in eng.finished}


# ---------------------------------------------------------------------------
# fault-plan grammar
# ---------------------------------------------------------------------------


def test_fault_plan_parse():
    inj = FaultInjector.parse(
        "tick=6,kind=fail,device=7; tick=4,kind=raise,times=3;"
        "tick=5, kind=stall, ms=250, device=3")
    kinds = {f.kind: f for f in inj.faults}
    assert kinds["fail"].device == 7 and kinds["fail"].times > 1_000_000
    assert kinds["raise"].times == 3 and kinds["raise"].tick == 4
    assert kinds["stall"].ms == 250.0 and kinds["stall"].times == 1


@pytest.mark.parametrize("plan,msg", [
    ("tick=3", "kind= are required"),
    ("kind=raise", "tick= and kind"),
    ("tick=3,kind=melt", "not one of"),
    ("tick=3,kind=fail", "needs device="),
    ("tick=x,kind=raise", "bad value"),
    ("tick=3,kind=raise,volts=9", "unknown fault-plan key"),
    ("", "no clauses"),
    ("tick,kind=raise", "not key=value"),
])
def test_fault_plan_parse_errors(plan, msg):
    with pytest.raises(ValueError, match=msg):
        FaultInjector.parse(plan)


def test_fault_plan_from_env(monkeypatch):
    monkeypatch.delenv("REPRO_FAULT_PLAN", raising=False)
    assert FaultInjector.from_env() is None
    monkeypatch.setenv("REPRO_FAULT_PLAN", "tick=2,kind=raise")
    inj = FaultInjector.from_env()
    assert inj is not None and inj.faults[0].kind == "raise"


def test_fault_firing_semantics():
    f = Fault(tick=3, kind="raise", times=2)
    assert not f.due(2) and f.due(3) and f.due(99)
    inj = FaultInjector([f])
    for _ in range(2):
        with pytest.raises(InjectedFault):
            inj.on_tick(5)
    inj.on_tick(5)                      # spent: no further fires
    assert f.fired == 2
    assert inj.suspect_devices() == set()   # unattributed


# ---------------------------------------------------------------------------
# health: structured reasons + injected overlay
# ---------------------------------------------------------------------------


def test_health_reports_structured_reason():
    reports = check_devices()
    assert all(r.ok and r.reason is HealthReason.OK for r in reports)
    bad = DeviceHealth(device=3, ok=False, latency_s=0.1,
                       reason=HealthReason.CHECKSUM_MISMATCH, detail="x!=y")
    # legacy string surface derives from the enum — no parsing anywhere
    assert bad.error == "checksum_mismatch: x!=y"
    assert DeviceHealth(device="d0", ok=True, latency_s=0.0).error == ""


def test_injected_health_overlay():
    devs = jax.devices()[:1]
    inj = FaultInjector.parse(f"tick=2,kind=fail,device={devs[0].id}")
    reports = inj.apply_health(check_devices(devs), devs, tick=1)
    assert all(r.ok for r in reports)       # not armed yet
    reports = inj.apply_health(check_devices(devs), devs, tick=2)
    assert not reports[0].ok
    assert reports[0].reason is HealthReason.INJECTED
    assert inj.suspect_devices() == {devs[0].id}


# ---------------------------------------------------------------------------
# straggler monitor: warn -> remesh -> abort ladder + window edges
# ---------------------------------------------------------------------------


def test_straggler_ladder_direct():
    mon = StragglerMonitor(window=8, warn_ratio=1.5, remesh_ratio=2.5,
                           abort_ratio=5.0, sustained=2, min_window=2)
    assert mon.observe(0, 0.1).action == "ok"       # warmup
    assert mon.observe(1, 0.1).action == "ok"
    assert mon.observe(2, 0.2).action == "ok"       # outlier 1 of sustained=2
    assert mon.observe(3, 0.2).action == "warn"     # sustained 2x median
    assert mon.observe(4, 0.3).action == "remesh"   # 3x >= remesh_ratio
    assert mon.observe(5, 0.6).action == "abort"    # 6x >= abort_ratio
    assert mon.observe(6, 0.1).action == "ok"       # recovery resets _over
    assert mon.observe(7, 0.2).action == "ok"       # counter restarted


def test_straggler_short_window_never_escalates():
    mon = StragglerMonitor(min_window=4, sustained=1, warn_ratio=1.1)
    # a lone huge sample during warmup is not an outlier — there is no
    # baseline yet (median of < min_window samples is just the sample)
    for i, t in enumerate([5.0, 0.1, 9.0, 0.1]):
        assert mon.observe(i, t).action == "ok"


def test_straggler_step_end_unpaired_is_ok():
    mon = StragglerMonitor()
    rep = mon.step_end(0)               # no step_start: tolerated
    assert rep.action == "ok" and rep.step_time == 0.0
    assert len(mon.times) == 0          # window unpolluted


def test_straggler_reset_clears_escalation():
    mon = StragglerMonitor(window=8, warn_ratio=1.5, sustained=1,
                           min_window=2)
    mon.observe(0, 0.1), mon.observe(1, 0.1)
    assert mon.observe(2, 0.2).action == "warn"
    mon.reset()
    assert mon._over == 0 and len(mon.times) == 0
    assert mon.observe(3, 0.2).action == "ok"       # re-warming


# ---------------------------------------------------------------------------
# elastic: survivor-mesh edges
# ---------------------------------------------------------------------------


def test_best_mesh_shape_survivors_below_tp_raises():
    with pytest.raises(ValueError, match="TP group"):
        best_mesh_shape(3, model_size=4)


def test_best_mesh_shape_one_device_degenerate():
    assert best_mesh_shape(1, model_size=1) == (1, 1)
    assert best_mesh_shape(7, model_size=4) == (1, 4)   # 3 idle survivors


def test_plan_remesh_dp_shrink_bumps_microbatches():
    from repro.core.topology import make_plan
    cfg = get_smoke_config("gemma-2b")
    old = make_plan(cfg, {"data": 4, "model": 2})
    dec = plan_remesh(cfg, old_plan=old, n_surviving=6, global_batch=24,
                      seq_len=128, old_microbatches=1)
    assert dec.mesh_shape == (3, 2)
    assert dec.microbatches == 2        # DP 4->3: ceil(4/3) grad-accum bump
    assert dec.dropped == 2
    assert "preserved" in dec.note


@needs8
def test_evacuation_mesh_preserves_tp_axis():
    devs = jax.devices()
    mesh = evacuation_mesh(devs[:7], tp=4)
    assert dict(zip(mesh.axis_names, mesh.devices.shape)) == \
        {"data": 1, "model": 4}
    with pytest.raises(ValueError, match="TP group"):
        evacuation_mesh(devs[:3], tp=4)


# ---------------------------------------------------------------------------
# engine: retry, evacuation, token parity (single device, in-place rebuild)
# ---------------------------------------------------------------------------


def test_transient_fault_absorbed_by_retry():
    cfg = _cfg()
    base = _tokens(_run(cfg))
    eng = _run(cfg, injector=FaultInjector.parse("tick=3,kind=raise"),
               tick_retries=2)
    assert eng.stats.tick_retries == 1 and eng.stats.evacuations == 0
    assert _tokens(eng) == base


def test_retry_exhaustion_evacuates_dense_parity():
    cfg = _cfg()
    base = _tokens(_run(cfg))
    eng = _run(cfg, injector=FaultInjector.parse("tick=3,kind=raise,times=3"),
               tick_retries=2)
    assert eng.stats.evacuations == 1
    assert _tokens(eng) == base         # identical streams, zero dropped
    evs = [e["event"] for e in eng.ft_events]
    assert evs.count("tick_retry") == 3 and "evacuate" in evs


def test_evacuation_paged_parity_and_prefix_recovery():
    cfg = _cfg()
    base = _tokens(_run(cfg, kv_layout="paged", block_size=8))
    eng = _run(cfg, kv_layout="paged", block_size=8,
               injector=FaultInjector.parse("tick=4,kind=raise,times=3"),
               tick_retries=2)
    assert eng.stats.evacuations == 1
    assert _tokens(eng) == base
    # the evacuation recorded the portable block chains of the live slots
    ev = next(e for e in eng.ft_events if e["event"] == "evacuate")
    assert ev["kv_chains"] and all(c for c in ev["kv_chains"].values())
    # rebuilt pool re-registered the shared prefix and drained clean
    assert eng.pool.prefix_hits >= 2
    assert eng.pool.used_blocks == 0


def test_health_gated_evacuation_single_device():
    cfg = _cfg()
    base = _tokens(_run(cfg))
    dev = jax.devices()[0].id
    # device 0 "fails" once: with no surviving-mesh alternative on one
    # device this is the in-place rebuild path (process-level fault)
    eng = _run(cfg, injector=FaultInjector.parse(
        f"tick=2,kind=fail,device={dev},times=1"), health_every=2)
    assert eng.stats.health_checks >= 1
    assert eng.stats.evacuations == 1
    assert _tokens(eng) == base
    ev = next(e for e in eng.ft_events if e["event"] == "health")
    assert ev["failed"][0]["reason"] == HealthReason.INJECTED.value


def test_stall_fault_walks_straggler_ladder():
    cfg = _cfg()
    base = _tokens(_run(cfg))
    # sustained 300ms stalls against ~10ms CPU ticks: ratio >> remesh_ratio
    # (tick=6 leaves the post-compile warmup window stall-free, so the
    # rolling median is a genuine steady-state baseline)
    eng = _run(cfg, injector=FaultInjector.parse(
        "tick=6,kind=stall,ms=300,times=8"),
        straggler_kw=dict(window=16, warn_ratio=2.5, remesh_ratio=4.0,
                          abort_ratio=1e9, sustained=2, min_window=2))
    assert eng.stats.evacuations >= 1
    assert _tokens(eng) == base
    acts = [e["action"] for e in eng.ft_events if e["event"] == "straggler"]
    assert "remesh" in acts or "warn" in acts


def test_repeated_evacuation_gives_up():
    cfg = _cfg()
    rt = Runtime.create(cfg, shape_kind="decode", capacity=32)
    eng = rt.engine(num_slots=2, tick_retries=0, retry_backoff_s=0.0,
                    max_evacuations=2,
                    injector=FaultInjector.parse(
                        "tick=1,kind=raise,times=1000"))
    for r in _stream(cfg):
        eng.submit(r)
    with pytest.raises(RuntimeError, match="giving up after 2 evacuations"):
        eng.run_to_completion()


def test_step_compile_error_raises_without_retry_or_evacuation():
    """A step that fails to lower is a program error, not a fault: it
    raises at the first dispatch and never walks the retry/evacuation
    ladder.  Compiled (non-interpret) Pallas has no CPU lowering, so the
    forced-Pallas decode step is refused here the way a Mosaic refusal
    would be on a chip."""
    from repro.kernels import ops
    from repro.serve.engine import StepCompileError
    cfg = _cfg()
    rt = Runtime.create(cfg, shape_kind="decode", capacity=32)
    eng = rt.engine(num_slots=2, attn_impl="pallas", injector=None,
                    retry_backoff_s=0.0)
    for r in _stream(cfg):
        eng.submit(r)
    ops.set_interpret_mode(False)
    try:
        with pytest.raises(StepCompileError, match="'decode'"):
            eng.run_to_completion()
    finally:
        ops.set_interpret_mode(None)
    assert eng.stats.tick_retries == 0
    assert eng.stats.evacuations == 0
    assert eng.ft_events == []


def test_engine_injector_defaults_from_env(monkeypatch):
    cfg = _cfg()
    monkeypatch.setenv("REPRO_FAULT_PLAN", "tick=3,kind=raise")
    eng = _run(cfg, injector=None)          # explicit None disables
    assert eng.stats.tick_retries == 0
    rt = Runtime.create(cfg, shape_kind="decode", capacity=32)
    eng2 = rt.engine(num_slots=2)           # default: parses the env plan
    assert eng2.injector is not None
    assert eng2.injector.faults[0].kind == "raise"


def test_runtime_describe_ft_block():
    rt = Runtime.create(_cfg(), shape_kind="decode", capacity=32)
    desc = rt.describe()
    assert "ft        :" in desc and "fault_plan=" in desc
    assert "evac(lose-1)" in desc


# ---------------------------------------------------------------------------
# warm restart: EngineSnapshot
# ---------------------------------------------------------------------------


def test_engine_snapshot_roundtrip(tmp_path):
    cfg = _cfg()
    base = _tokens(_run(cfg))

    rt = Runtime.create(cfg, shape_kind="decode", capacity=32)
    eng = rt.engine(num_slots=2, retry_backoff_s=0.001)
    for r in _stream(cfg):
        eng.submit(r)
    for _ in range(4):                      # interrupt mid-serve
        eng.tick()
    snap = eng.snapshot()
    assert snap.requests and snap.meta["arch"] == cfg.name
    path = snap.save(str(tmp_path / "snap"))
    back = EngineSnapshot.load(path)
    assert back.requests == snap.requests

    # "restart": a fresh engine continues every stream exactly
    eng2 = Runtime.create(cfg, shape_kind="decode",
                          capacity=32).engine(num_slots=2)
    assert eng2.load_snapshot(back) == len(back.requests)
    eng2.run_to_completion()
    merged = _tokens(eng)                   # requests finished pre-snapshot
    merged.update(_tokens(eng2))
    assert merged == base
    assert len(merged) == 7


def test_engine_snapshot_load_requires_idle():
    cfg = _cfg()
    rt = Runtime.create(cfg, shape_kind="decode", capacity=32)
    eng = rt.engine(num_slots=2)
    eng.submit(_stream(cfg)[0])
    with pytest.raises(RuntimeError, match="idle engine"):
        eng.load_snapshot(EngineSnapshot())


def test_engine_snapshot_load_rejects_wrong_arch():
    cfg = _cfg()
    eng = Runtime.create(cfg, shape_kind="decode",
                         capacity=32).engine(num_slots=2)
    with pytest.raises(ValueError, match="arch"):
        eng.load_snapshot(EngineSnapshot(meta={"arch": "other-arch"}))


def test_engine_snapshot_load_missing(tmp_path):
    with pytest.raises(FileNotFoundError, match="no engine snapshot"):
        EngineSnapshot.load(str(tmp_path / "nope"))


# ---------------------------------------------------------------------------
# the mesh-shrink path: 2x4 -> 1x4 after losing a device (8-device gate)
# ---------------------------------------------------------------------------


@needs8
def test_evacuation_shrinks_mesh_token_parity():
    from repro.launch.mesh import mesh_from_spec
    cfg = _cfg()
    base = _tokens(_run(cfg, mesh=mesh_from_spec("2x4")))

    victim = jax.devices()[7].id
    eng = _run(cfg, mesh=mesh_from_spec("2x4"), health_every=2,
               injector=FaultInjector.parse(
                   f"tick=2,kind=fail,device={victim}"))
    assert eng.stats.evacuations == 1
    # TP axis preserved, DP absorbed the loss: 2x4 -> 1x4 on 7 survivors
    assert dict(zip(eng.mesh.axis_names, eng.mesh.devices.shape)) == \
        {"data": 1, "model": 4}
    assert victim not in {d.id for d in eng.mesh.devices.flatten()}
    assert _tokens(eng) == base         # identical streams across the move


@needs8
def test_evacuation_all_tp_groups_lost_raises():
    from repro.launch.mesh import mesh_from_spec
    cfg = _cfg()
    rt = Runtime.create(cfg, mesh_from_spec("2x4"), shape_kind="decode",
                        capacity=32)
    # 5 dead devices leave 3 survivors < one TP group of 4: evacuation
    # must fail fast with the checkpoint-restore hint, not wedge
    plan = ";".join(f"tick=2,kind=fail,device={d.id}"
                    for d in jax.devices()[:5])
    eng = rt.engine(num_slots=2, health_every=2,
                    injector=FaultInjector.parse(plan),
                    retry_backoff_s=0.001)
    for r in _stream(cfg):
        eng.submit(r)
    with pytest.raises(ValueError, match="TP group"):
        eng.run_to_completion()
