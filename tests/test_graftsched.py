"""graftsched: per-site pass schedules with verified receipts, searched
jointly by the autotuner (analysis/passes.py PassSchedule +
analysis/autotune.py autotune_train_schedules; docs/PASSES.md
"Schedules").

Contracts under test:

- site-aware passes enumerate STABLE site ids (eqn paths into the
  inlined jaxpr) — identical across two independent traces of the same
  program;
- ``PassSchedule`` canonicalization: site order never changes the
  hash, ``from_dict(canonical())`` round-trips, the all-sites schedule
  hashes identically to the legacy ``passes=`` tuple it desugars to;
- a partial schedule installs exactly the enabled sites, the receipt
  carries one row per site, and the per-site deltas SUM to the
  whole-receipt cost delta (1 % acceptance bound; exact by
  construction);
- the all-sites schedule is bitwise-equivalent to the legacy on/off
  path (same losses, same compile-cache key → warm hit);
- schedule-keyed compile caching: same program + different schedule →
  distinct CompileCache entries; identical schedule → cross-process
  hit at ZERO XLA compiles;
- ``autotune_train_schedules``: 100 % ledger accounting, rejected
  candidates carry ``zero_compile=True`` with zero compiles spent —
  all through ``analyze_cost``-grade abstract traces, no XLA compile.

Everything runs on the tiny dense nets.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, nd
from incubator_mxnet_tpu.analysis.autotune import (autotune_train_schedules,
                                                   default_schedule_space,
                                                   dense_workload,
                                                   schedule_site_table)
from incubator_mxnet_tpu.analysis.passes import (PassContext, PassManager,
                                                 PassSchedule, get_pass,
                                                 resolve_schedule)
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.parallel import aot, make_train_step
from incubator_mxnet_tpu.parallel.distributed import collectives_supported

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mlp_program(seed=7):
    """Abstract inference jaxpr of the 2-layer test MLP + its param
    values (probe overrides) — the direct-PassManager harness."""
    from incubator_mxnet_tpu.gluon.block import pure_forward

    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu"), nn.Dense(10))
    net.initialize(init=mx.init.Xavier())
    net(nd.ones((2, 16)))
    params = list(net.collect_params().values())
    p_vals = [p._data._data for p in params]

    def infer(pv, x):
        out, _tc = pure_forward(net, params, pv, x, training=False)
        return out

    closed = jax.make_jaxpr(infer)(
        [jax.ShapeDtypeStruct(v.shape, v.dtype) for v in p_vals],
        jax.ShapeDtypeStruct((4, 16), np.float32))
    ctx = PassContext(param_invars=frozenset(range(len(p_vals))),
                      probe_overrides=dict(enumerate(p_vals)),
                      where="test_graftsched")
    return closed, ctx


def _amp_step(schedule=None, seed=3, **kw):
    """3x Dense(16) train step with amp_bf16 — ``schedule`` may be a
    legacy name tuple, a PassSchedule or a canonical dict (the
    subprocess leg re-hydrates from JSON)."""
    mx.random.seed(seed)
    net = nn.HybridSequential()
    for _ in range(3):
        net.add(nn.Dense(16, activation="tanh"))
    net.initialize(init=mx.init.Xavier())
    net(nd.ones((2, 16)))
    step = make_train_step(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                           optimizer="sgd", learning_rate=0.1, momentum=0.9,
                           lint="off", cost="off",
                           passes=schedule if schedule is not None
                           else ("amp_bf16",), **kw)
    rng = np.random.RandomState(0)
    x = nd.array(rng.rand(8, 16).astype(np.float32))
    y = nd.array((np.arange(8) % 4).astype(np.float32))
    return step, x, y


# ---------------------------------------------------------------------------
# site enumeration + schedule canonicalization
# ---------------------------------------------------------------------------

def test_site_enumeration_stable_ids():
    closed, ctx = _mlp_program()
    amp = get_pass("amp_bf16")
    q8 = get_pass("quantize_int8")
    assert amp.site_aware and q8.site_aware
    ids = [s.id for s in amp.enumerate_sites(closed, ctx)]
    assert ids == ["dot_general:0", "dot_general:1"]
    qids = [s.id for s in q8.enumerate_sites(closed, ctx)]
    assert qids and all(i.startswith("invar:") for i in qids)
    # stability across an independent retrace of the same model
    closed2, ctx2 = _mlp_program()
    assert [s.id for s in amp.enumerate_sites(closed2, ctx2)] == ids
    assert [s.id for s in q8.enumerate_sites(closed2, ctx2)] == qids
    # sites carry the local unfused weights the delta attribution uses
    s0 = amp.enumerate_sites(closed, ctx)[0]
    assert s0.kind == "eqn" and s0.flops > 0 and s0.hbm_bytes > 0


def test_schedule_canonical_hash_roundtrip():
    a = PassSchedule([("amp_bf16", {"dot_general:0": True,
                                    "dot_general:1": False}),
                      ("cse_dead_aux", True)])
    b = PassSchedule([("amp_bf16", {"dot_general:1": False,
                                    "dot_general:0": True}),
                      ("cse_dead_aux", True)])
    assert a.hash() == b.hash()  # site order never changes the hash
    assert PassSchedule.from_dict(a.canonical()).hash() == a.hash()
    # the legacy passes= tuple IS the all-sites schedule
    legacy = PassSchedule.from_passes(("amp_bf16", "cse_dead_aux"))
    allon = PassSchedule([("amp_bf16", True), ("cse_dead_aux", True)])
    assert legacy.hash() == allon.hash()
    # two different schedules never share a hash
    assert a.hash() != allon.hash()
    off = PassSchedule([("amp_bf16", False), ("cse_dead_aux", True)])
    assert off.hash() != allon.hash()
    assert not off.enabled("amp_bf16") and off.enabled("cse_dead_aux")
    assert a.sites_for("amp_bf16") == frozenset({"dot_general:0"})
    # resolve_schedule: dict and PassSchedule in, (passes, schedule) out
    ps, sched = resolve_schedule(a.canonical())
    assert [p.name for p in ps] == ["amp_bf16", "cse_dead_aux"]
    assert sched.hash() == a.hash()
    ps2, sched2 = resolve_schedule("amp_bf16,cse_dead_aux")
    assert sched2 is None and [p.name for p in ps2] == ["amp_bf16",
                                                        "cse_dead_aux"]


# ---------------------------------------------------------------------------
# partial schedules: receipts, per-site delta attribution
# ---------------------------------------------------------------------------

def test_partial_schedule_installs_enabled_sites_only():
    closed, ctx = _mlp_program()
    sched = PassSchedule([("amp_bf16", {"dot_general:1": True})])
    res = PassManager(None, schedule=sched, raise_on_error=False).run(
        closed, ctx)
    (r,) = res.receipts
    assert r.installed and r.hits == 1
    rows = {row["site"]: row for row in r.sites}
    assert rows["dot_general:0"]["decision"] is False
    assert not rows["dot_general:0"]["installed"]
    assert rows["dot_general:0"]["hbm_bytes_delta"] == 0.0
    assert rows["dot_general:1"]["decision"] is True
    assert rows["dot_general:1"]["installed"]


def test_per_site_deltas_sum_to_receipt_delta():
    """Acceptance bound: per-site receipts sum to the whole-schedule
    CostReport delta within 1 % (exact by construction)."""
    closed, ctx = _mlp_program()
    res = PassManager(["quantize_int8", "amp_bf16"]).run(closed, ctx)
    for r in res.receipts:
        assert r.installed, r.name
        assert r.sites, r.name
        for field in ("hbm_bytes", "flops", "param_bytes"):
            whole = getattr(r, field + "_after") - \
                getattr(r, field + "_before")
            part = sum(row[field + "_delta"] for row in r.sites)
            tol = max(abs(whole) * 0.01, 1e-6)
            assert abs(part - whole) <= tol, (r.name, field, part, whole)
        # installed sites with a concrete probe report probe_ok=True
        assert all(row["probe_ok"] for row in r.sites
                   if row["installed"]), r.name


def test_disabled_pass_and_gl304_no_match():
    closed, ctx = _mlp_program()
    # whole pass off: a deliberate decision, NOT a GL304 no-op warning
    sched = PassSchedule([("amp_bf16", False)])
    res = PassManager(None, schedule=sched, raise_on_error=False).run(
        closed, ctx)
    assert not res.receipts[0].installed
    assert "disabled by schedule" in (res.receipts[0].notes or "")
    assert not any(d.code == "GL304" for d in res.diagnostics)
    # a schedule naming sites that do not exist IS a GL304 no-op
    ghost = PassSchedule([("amp_bf16", {"dot_general:99": True})])
    res2 = PassManager(None, schedule=ghost, raise_on_error=False).run(
        closed, ctx)
    assert not res2.receipts[0].installed
    assert any(d.code == "GL304" for d in res2.diagnostics)


# ---------------------------------------------------------------------------
# all-sites schedule == legacy passes= (sugar, bitwise)
# ---------------------------------------------------------------------------

def test_all_sites_schedule_bitwise_equals_legacy(tmp_path):
    cache = aot.CompileCache(str(tmp_path))
    step_a, x, y = _amp_step(("amp_bf16",))
    assert step_a.aot_compile(x, y, cache=cache)["cache"] == "stored"
    losses_a = [float(step_a(x, y).asscalar()) for _ in range(3)]

    sched = PassSchedule.from_passes(("amp_bf16",))
    step_b, x2, y2 = _amp_step(sched)
    assert step_b.schedule_hash == step_a.schedule_hash
    c0 = aot.XLA_COMPILES.count
    t = step_b.aot_compile(x2, y2, cache=cache)
    assert t["cache"] == "hit"  # same program, same schedule key
    assert aot.XLA_COMPILES.count == c0
    losses_b = [float(step_b(x2, y2).asscalar()) for _ in range(3)]
    assert losses_a == losses_b  # bitwise: the on/off path is sugar


# ---------------------------------------------------------------------------
# schedule-keyed compile caching
# ---------------------------------------------------------------------------

def test_different_schedules_distinct_cache_entries(tmp_path):
    """Two schedules of the SAME pass list never collide in the
    compile cache — even when they lower to the same bytes."""
    cache = aot.CompileCache(str(tmp_path))
    step_a, x, y = _amp_step(PassSchedule.from_passes(("amp_bf16",)))
    partial = PassSchedule([("amp_bf16", {"dot_general:0": True})])
    step_b, _, _ = _amp_step(partial)
    assert step_a.schedule_hash != step_b.schedule_hash
    assert step_a._cache_extra() != step_b._cache_extra()
    assert step_a.aot_compile(x, y, cache=cache)["cache"] == "stored"
    t = step_b.aot_compile(x, y, cache=cache)
    assert t["cache"] == "stored"  # distinct entry, no false hit
    assert cache.hits == 0


def test_same_schedule_cross_process_zero_compiles(tmp_path):
    """A fresh process rebuilding the SAME partial schedule performs 0
    XLA compiles (the retune-after-restart contract)."""
    if not collectives_supported():
        pytest.skip("backend cannot run the subprocess leg")
    sched = PassSchedule([("amp_bf16", {"dot_general:0": True,
                                        "dot_general:1": True,
                                        "dot_general:2": False})])
    cache = aot.CompileCache(str(tmp_path))
    step, x, y = _amp_step(sched)
    assert step.aot_compile(x, y, cache=cache)["cache"] == "stored"
    loss_ref = float(step(x, y).asscalar())

    child = subprocess.run(
        [sys.executable, "-c", """
import sys, json
sys.path.insert(0, %r)
from _platform_pin import pin_cpu
jax = pin_cpu(8)
jax.config.update("jax_default_matmul_precision", "highest")
from tests.test_graftsched import _amp_step
from incubator_mxnet_tpu.analysis.passes import PassSchedule
from incubator_mxnet_tpu.parallel import aot
sched = PassSchedule.from_dict(json.loads(%r))
step, x, y = _amp_step(sched)
t = step.aot_compile(x, y)
print(json.dumps({"cache": t["cache"], "compiles": aot.XLA_COMPILES.count,
                  "sched": step.schedule_hash,
                  "loss": float(step(x, y).asscalar())}))
""" % (REPO, sched.to_json())],
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 MXTPU_COMPILE_CACHE=str(tmp_path)),
        capture_output=True, text=True, cwd=REPO, timeout=300)
    assert child.returncode == 0, child.stderr[-2000:]
    rec = json.loads(child.stdout.strip().splitlines()[-1])
    assert rec["sched"] == sched.hash()
    assert rec["cache"] == "hit"
    assert rec["compiles"] == 0  # ZERO XLA compiles in the new process
    assert rec["loss"] == loss_ref


# ---------------------------------------------------------------------------
# the joint search
# ---------------------------------------------------------------------------

def test_schedule_search_ledger_and_winner_config():
    mk, mb, loss_fn = dense_workload()
    c0 = aot.XLA_COMPILES.count
    res = autotune_train_schedules(mk, mb, loss_fn,
                                   passes=("cse_dead_aux", "amp_bf16"),
                                   knobs={"batch": 8}, device="cpu-proxy",
                                   budget_compiles=0)
    assert aot.XLA_COMPILES.count == c0  # ranking never compiles
    assert res.compiles_spent == 0
    assert res.candidates and all(c.zero_compile for c in res.candidates)
    assert all(c.status == "predicted" for c in res.candidates)
    hashes = [c.knobs["schedule_hash"] for c in res.candidates]
    assert len(set(hashes)) == len(hashes)  # deduped space
    cfg = res.winner_config()  # predicted-only winner (budget 0)
    assert cfg is not None and cfg["knobs"]["schedule_hash"] in hashes
    assert cfg["measured_s_per_sample"] is None
    # the persisted schedule round-trips into a runnable step
    ps, sched = resolve_schedule(cfg["knobs"]["schedule"])
    assert sched.hash() == cfg["knobs"]["schedule_hash"]


def test_schedule_search_rejects_over_budget_zero_compile():
    mk, mb, loss_fn = dense_workload()
    c0 = aot.XLA_COMPILES.count
    res = autotune_train_schedules(mk, mb, loss_fn,
                                   passes=("cse_dead_aux", "amp_bf16"),
                                   knobs={"batch": 8}, device="cpu-proxy",
                                   hbm_budget=1.0,  # 1 byte: nothing fits
                                   budget_compiles=0)
    assert aot.XLA_COMPILES.count == c0
    rejected = [c for c in res.candidates
                if c.status == "rejected-infeasible"]
    assert rejected and all(c.zero_compile for c in rejected)
    assert all("GL201" in (c.reason or "") for c in rejected)
    assert res.winner is None and res.winner_config() is None
