"""graftpass: the verified trace-time jaxpr→jaxpr rewrite engine
(analysis/passes.py, docs/PASSES.md, GL301–GL303 in docs/ANALYSIS.md).

The acceptance surface of ROADMAP item 5:

- a contract-violating pass trips GL301 and is NOT installed — refused
  at trace time with zero compiles spent (train step and manager);
- a pass that introduces a graftlint finding trips the GL302 re-lint
  gate and is refused;
- quantize / AMP / space-to-depth / CSE golden parity on the dense MLP,
  the conv stem and the fused train step (dp-mesh leg under
  ``lint="error"`` + ``cost="check"``);
- cost receipts: predicted HBM bytes strictly drop for space_to_depth
  and cse_dead_aux; param bytes drop ~4x for quantize_int8;
- the ServeEngine int8 tier rides the pass path: ``dtype="int8"`` ==
  ``passes=("quantize_int8",)`` bitwise, with 0 post-warmup recompiles;
- the autotuner ranks pass on/off knobs and rejects GL301 pipelines
  with zero compiles;
- the tools/graftpass.py CLI gate (exit 1 on contract violation).

Budget discipline: tiny nets, no mesh wider than 8 forged CPU devices,
heavy soaks stay out (the suite is at its 870 s ceiling).
"""
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, nd
from incubator_mxnet_tpu.analysis import CODES, LintError, Severity
from incubator_mxnet_tpu.analysis.passes import (Contract, GraftPass,
                                                 PASS_REGISTRY,
                                                 PassContext, PassManager,
                                                 PassResult, _default_bind,
                                                 get_pass, register_pass,
                                                 resolve_passes, retrace)
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.parallel import aot, make_mesh, make_train_step
from incubator_mxnet_tpu.serve import ServeEngine

SAMPLE = (16,)


def _mlp(seed=7):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu"), nn.Dense(10))
    net.initialize(init=mx.init.Xavier())
    net(nd.ones((2,) + SAMPLE))
    return net


def _dense_step(passes=None, seed=3, mesh=None, **kw):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    for _ in range(3):
        net.add(nn.Dense(16, activation="tanh"))
    net.initialize(init=mx.init.Xavier())
    net(nd.ones((2, 16)))
    return make_train_step(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                           optimizer="sgd", learning_rate=0.1, momentum=0.9,
                           mesh=mesh, passes=passes, **kw)


def _batch(b=16):
    rng = np.random.RandomState(0)
    return (nd.array(rng.rand(b, 16).astype(np.float32)),
            nd.array((np.arange(b) % 4).astype(np.float32)))


class _ValueBreaker(GraftPass):
    """Deliberately wrong rewrite: perturbs every matmul output — must
    trip GL301 on the concrete probe under any contract."""

    name = "_test_value_breaker"
    contract = Contract.bit_exact()

    def run(self, closed, ctx):
        hits = [0]

        def rule(eqn, invals):
            if eqn.primitive.name == "dot_general":
                hits[0] += 1
                return [o * 1.001 for o in _default_bind(eqn, invals)]
            return None

        new = retrace(closed, rule)
        return PassResult(new, hits=hits[0])


# ---------------------------------------------------------------------------
# catalog, registry, resolution
# ---------------------------------------------------------------------------

def test_gl3xx_cataloged():
    assert CODES["GL301"][0] == Severity.ERROR
    assert CODES["GL302"][0] == Severity.ERROR
    assert CODES["GL303"][0] == Severity.WARNING


def test_registry_and_resolution(monkeypatch):
    for name in ("quantize_int8", "quantize_int4", "amp_bf16",
                 "space_to_depth", "cse_dead_aux"):
        assert name in PASS_REGISTRY
        assert get_pass(name).name == name
    assert resolve_passes("cse_dead_aux, amp_bf16")[1].name == "amp_bf16"
    assert resolve_passes(()) == ()
    with pytest.raises(ValueError, match="unknown graftpass"):
        get_pass("fuse_everything")
    # env resolution: explicit arg > MXTPU_PASSES > ()
    monkeypatch.setenv("MXTPU_PASSES", "cse_dead_aux")
    s = _dense_step(lint="off")
    assert [p.name for p in s._passes] == ["cse_dead_aux"]
    s2 = _dense_step(passes=(), lint="off")
    assert s2._passes == ()
    monkeypatch.delenv("MXTPU_PASSES")
    assert _dense_step(lint="off")._passes == ()


def test_contract_check_semantics():
    a = np.array([[1.0, 2.0, 3.0]], np.float32)
    ok, d = Contract.bit_exact().check([a], [a.copy()])
    assert ok and d["bitwise"]
    ok, _ = Contract.bit_exact().check([a], [a + 1e-7])
    assert not ok
    ok, d = Contract.tolerance(0.1).check([a], [a + 0.2])
    assert ok and d["max_abs_err"] == pytest.approx(0.2)
    ok, _ = Contract.tolerance(0.01).check([a], [a + 0.2])
    assert not ok
    # argmax: decided rankings must hold; within-margin ties may flip
    ref = np.array([[0.0, 1.0], [0.0, 0.001]], np.float32)
    flip_tie = np.array([[0.0, 1.0], [0.001, 0.0]], np.float32)
    ok, d = Contract.argmax_preserving(0.05).check([ref], [flip_tie])
    assert ok and d["argmax_rows_checked"] == 1
    flip_decided = np.array([[1.0, 0.0], [0.0, 0.001]], np.float32)
    ok, _ = Contract.argmax_preserving(0.05).check([ref], [flip_decided])
    assert not ok


# ---------------------------------------------------------------------------
# the four shipped passes, at the manager level
# ---------------------------------------------------------------------------

def test_cse_dead_aux_merges_and_drops_with_receipts():
    def f(x, w):
        m1 = jnp.mean(x)
        m2 = jnp.mean(x)            # duplicate of m1
        _dead = (x @ w) @ w.T       # dead MXU work, noqa: F841
        return (x - m1) * m2

    cj = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((128, 128), jnp.float32),
                           jax.ShapeDtypeStruct((128, 128), jnp.float32))
    res = PassManager(["cse_dead_aux"]).run(cj, PassContext())
    r = res.receipts[0]
    assert r.installed and r.hits >= 2
    assert r.hbm_bytes_after < r.hbm_bytes_before   # strict drop
    assert r.probe["bitwise"] is True
    assert res.changed
    # round-trips through the stable JSON schema
    json.dumps([q.to_dict() for q in res.receipts])


def test_space_to_depth_bit_exact_and_bytes_drop():
    from jax import lax

    def conv1(x, w):
        return lax.conv_general_dilated(
            x, w, (2, 2), [(3, 3), (3, 3)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))

    cj = jax.make_jaxpr(conv1)(
        jax.ShapeDtypeStruct((2, 3, 16, 16), jnp.float32),
        jax.ShapeDtypeStruct((8, 3, 7, 7), jnp.float32))
    res = PassManager(["space_to_depth"]).run(cj, PassContext())
    r = res.receipts[0]
    assert r.installed and r.hits == 1
    assert r.probe["bitwise"] is True          # the bit_exact contract
    assert r.hbm_bytes_after < r.hbm_bytes_before   # strict drop
    assert r.flops_after < r.flops_before      # lane padding removed
    # golden parity on real floats (reassociation-level only)
    rng = np.random.RandomState(0)
    xv = rng.normal(size=(2, 3, 16, 16)).astype(np.float32)
    wv = rng.normal(size=(8, 3, 7, 7)).astype(np.float32)
    from incubator_mxnet_tpu.analysis.passes import eval_closed

    ref = np.asarray(eval_closed(cj, [xv, wv])[0])
    got = np.asarray(eval_closed(res.closed_jaxpr, [xv, wv])[0])
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=1e-5)

    # a stride-1 conv is not a target: the pass reports nothing to do
    def conv_s1(x, w):
        return lax.conv_general_dilated(
            x, w, (1, 1), [(3, 3), (3, 3)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))

    cj1 = jax.make_jaxpr(conv_s1)(
        jax.ShapeDtypeStruct((2, 3, 16, 16), jnp.float32),
        jax.ShapeDtypeStruct((8, 3, 7, 7), jnp.float32))
    res1 = PassManager(["space_to_depth"]).run(cj1, PassContext())
    assert not res1.changed and not res1.receipts[0].changed


def test_quantize_int8_engine_parity_and_zero_recompiles():
    """The refactored int8 tier: the quantize pass over the shared AOT
    build path — parity within 2 % of output scale, argmax identical,
    int8 resident weights, receipts stamped, 0 post-warmup recompiles,
    and ``dtype="int8"`` sugar bitwise-equal to the explicit pass."""
    net = _mlp()
    x = np.random.RandomState(4).rand(6, *SAMPLE).astype(np.float32)
    fp = ServeEngine(net, buckets=(8,), lint="error")
    fp.warmup(np.zeros(SAMPLE, np.float32))
    ref = np.asarray(fp.infer(x))

    e8 = ServeEngine(net, buckets=(4, 8), passes=("quantize_int8",),
                     lint="error")
    e8.warmup(np.zeros(SAMPLE, np.float32))
    got = np.asarray(e8.infer(x))
    tol = 0.02 * np.abs(ref).max()
    np.testing.assert_allclose(got, ref, atol=tol)
    assert np.argmax(got, 1).tolist() == np.argmax(ref, 1).tolist()
    quant = [v for v, q in zip(e8._p_vals, e8._quantized) if q]
    assert quant and all(v[0].dtype == np.int8 for v in quant)
    # receipts: the 4x resident-weight story, per bucket program
    assert len(e8.pass_receipts) == 2
    for receipts in e8.pass_receipts.values():
        r = receipts[0]
        assert r.installed and r.name == "quantize_int8"
        assert r.param_bytes_after < 0.35 * r.param_bytes_before
    # steady state never compiles
    rs = np.random.RandomState(2)
    for n in (1, 4, 6, 8, 3):
        e8.infer(rs.rand(n, *SAMPLE).astype(np.float32))
    assert e8.recompile_count == 0
    # dtype sugar is THE pass (the engine-private branch is gone)
    sugar = ServeEngine(net, buckets=(4, 8), dtype="int8", lint="error")
    sugar.warmup(np.zeros(SAMPLE, np.float32))
    np.testing.assert_array_equal(np.asarray(sugar.infer(x)), got)
    # hot swap re-quantizes the candidate through the same transform
    v2 = e8.update_params([np.asarray(p._data._data) * 1.02
                           for p in e8._params])
    assert v2 == 2 and e8.recompile_count == 0


def test_quantize_int4_tier_for_free():
    net = _mlp()
    x = np.random.RandomState(5).rand(4, *SAMPLE).astype(np.float32)
    fp = ServeEngine(net, buckets=(4,), lint="error")
    fp.warmup(np.zeros(SAMPLE, np.float32))
    ref = np.asarray(fp.infer(x))
    e4 = ServeEngine(net, buckets=(4,), passes=("quantize_int4",),
                     lint="error")
    e4.warmup(np.zeros(SAMPLE, np.float32))
    got = np.asarray(e4.infer(x))
    np.testing.assert_allclose(got, ref, atol=0.4 * np.abs(ref).max())
    codes = [np.asarray(v[0]) for v, q in zip(e4._p_vals, e4._quantized)
             if q]
    assert codes and all(c.dtype == np.int8 for c in codes)
    assert all(c.min() >= -7 and c.max() <= 7 for c in codes)


def test_amp_pass_on_train_step():
    x, y = _batch()
    s0 = _dense_step(lint="off")
    l0 = [float(s0(x, y).asscalar()) for _ in range(2)]
    s1 = _dense_step(passes=("amp_bf16",), lint="error")
    l1 = [float(s1(x, y).asscalar()) for _ in range(2)]
    assert np.allclose(l0, l1, rtol=0.05)
    r = s1.pass_receipts[0]
    assert r.installed and r.hits >= 2 and r.contract.startswith("tol")


def test_train_step_cse_dp_mesh_golden_parity():
    """The dp-mesh leg: zero=1 + cse_dead_aux under lint="error" +
    cost="check" — losses match the un-rewritten step to float noise
    (the pass is bit_exact; only XLA scheduling may differ) and the
    receipts carry the bitwise probe verdict."""
    x, y = _batch()
    mesh = make_mesh({"dp": 8}, devices=jax.devices()[:8])
    s0 = _dense_step(mesh=mesh, zero=1, lint="error", cost="check")
    l0 = [float(s0(x, y).asscalar()) for _ in range(3)]
    s1 = _dense_step(passes=("cse_dead_aux",), mesh=mesh, zero=1,
                     lint="error", cost="check")
    l1 = [float(s1(x, y).asscalar()) for _ in range(3)]
    np.testing.assert_allclose(l1, l0, rtol=1e-6)
    r = s1.pass_receipts[0]
    assert r.installed and r.probe["bitwise"] is True
    assert s1.cost_report is not None  # post-pass cost, GL201-gated


# ---------------------------------------------------------------------------
# the refusal gates
# ---------------------------------------------------------------------------

def test_gl301_contract_violation_refused_zero_compiles():
    """A deliberately wrong pass is refused at trace time: LintError
    naming GL301, no executable exists, no XLA compile was spent."""
    x, y = _batch()
    step = _dense_step(passes=(_ValueBreaker(),), lint="off")
    c0 = aot.XLA_COMPILES.count
    with pytest.raises(LintError, match="GL301"):
        step(x, y)
    assert step._compiled is None
    assert aot.XLA_COMPILES.count == c0
    # non-raising manager mode: the receipt says refused, not installed
    def f(a, b):
        return jnp.tanh(a @ b)

    cj = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((8, 8), jnp.float32),
                           jax.ShapeDtypeStruct((8, 8), jnp.float32))
    with pytest.warns(UserWarning, match="GL301"):
        res = PassManager([_ValueBreaker()],
                          raise_on_error=False).run(cj, PassContext())
    r = res.receipts[0]
    assert r.changed and not r.installed
    assert any(d.code == "GL301" for d in r.diagnostics)
    assert not res.changed  # the original program is what remains


def test_gl301_abstract_eval_interface_change_refused():
    class _Widens(GraftPass):
        name = "_test_widens"
        contract = Contract.bit_exact()

        def run(self, closed, ctx):
            jaxpr, consts = closed.jaxpr, closed.consts

            def wider(*args):
                outs = jax.core.eval_jaxpr(jaxpr, consts, *args)
                return [o.astype(jnp.float64) for o in outs]

            specs = [jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype)
                     for v in jaxpr.invars]
            return PassResult(jax.make_jaxpr(wider)(*specs), hits=1)

    cj = jax.make_jaxpr(lambda a: a * 2.0)(
        jax.ShapeDtypeStruct((4,), jnp.float32))
    with pytest.raises(LintError, match="GL301"):
        PassManager([_Widens()]).run(cj, PassContext())


def test_gl302_relint_gate_refuses_introduced_findings():
    """A rewrite that returns a donated invar as two outputs introduces
    a GL003 finding the input program did not have — the re-lint gate
    refuses it even though output avals match."""
    class _AliasesDonated(GraftPass):
        name = "_test_aliases_donated"
        contract = Contract.bit_exact()

        def run(self, closed, ctx):
            jaxpr = closed.jaxpr

            def evil(p, x):
                return p, p   # the donated invar, twice

            specs = [jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype)
                     for v in jaxpr.invars]
            return PassResult(jax.make_jaxpr(evil)(*specs), hits=1)

    def f(p, x):
        return p - x, p * 1.0   # two outputs with p's aval

    cj = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((8,), jnp.float32),
                           jax.ShapeDtypeStruct((8,), jnp.float32))
    ctx = PassContext(donated_leaves=(0,), probe="off")
    with pytest.raises(LintError, match="GL302"):
        PassManager([_AliasesDonated()]).run(cj, ctx)


def test_invar_change_refused_where_layout_is_pinned():
    """The train step pins its invar layout (donation/shardings): a
    quantize pass must no-op there, and an invar-changing result is a
    hard error under allow_invar_change=False."""
    x, y = _batch()
    s = _dense_step(passes=("quantize_int8",), lint="off")
    loss = float(s(x, y).asscalar())
    assert np.isfinite(loss)
    assert not s.pass_receipts[0].changed  # no eligible param invars
    # manager-level: an invar-splitting result against a pinned layout
    def g(w, x2):
        return x2 @ w

    cj = jax.make_jaxpr(g)(jax.ShapeDtypeStruct((8, 4), jnp.float32),
                           jax.ShapeDtypeStruct((2, 8), jnp.float32))
    ctx = PassContext(param_invars=frozenset([0]),
                      allow_invar_change=False, probe="off")
    with pytest.raises(ValueError, match="invar layout"):
        PassManager(["quantize_int8"]).run(cj, ctx)


# ---------------------------------------------------------------------------
# autotune: passes as on/off knobs
# ---------------------------------------------------------------------------

def test_autotune_ranks_pass_knobs_and_rejects_gl301_at_zero_compiles():
    from incubator_mxnet_tpu.analysis.autotune import (autotune_train,
                                                       default_train_space)

    register_pass("_test_value_breaker", _ValueBreaker())
    try:
        base = default_train_space({}, batches=(8,))
        crossed = default_train_space({}, batches=(8,),
                                      passes=("cse_dead_aux",))
        assert len(crossed) == 2 * len(base)
        assert {c["passes"] for c in crossed} == {(), ("cse_dead_aux",)}
        space = [
            {"batch": 8, "passes": ()},
            {"batch": 8, "passes": ("cse_dead_aux",)},
            {"batch": 8, "passes": ("_test_value_breaker",)},
        ]
        c0 = aot.XLA_COMPILES.count
        # the broken candidate is the default so it reaches the measure
        # phase: ranking is probe-free (zero eager executions), and the
        # GL301 probe fires at build time — BEFORE its compile
        res = autotune_train(space=space, budget_compiles=2,
                             warmup=1, iters=1,
                             default_knobs=space[2])
        assert res.accounted()
        broken = [c for c in res.candidates
                  if c.knobs["passes"] == ("_test_value_breaker",)][0]
        assert broken.status in ("rejected-invalid", "measure-error")
        assert "GL301" in broken.reason
        assert broken.compiles_spent == 0    # refused pre-compile
        ranked = [c for c in res.candidates
                  if c.knobs["passes"] != ("_test_value_breaker",)]
        assert all(c.pred_sps is not None for c in ranked)
        assert res.compiles_spent == aot.XLA_COMPILES.count - c0 <= 2
    finally:
        PASS_REGISTRY.pop("_test_value_breaker", None)


# ---------------------------------------------------------------------------
# the CLI gate (tools/graftpass.py)
# ---------------------------------------------------------------------------

def test_cli_list_and_json_schema(capsys):
    import tools.graftpass as gp

    assert gp.main(["--list", "--format", "json"]) == 0
    reg = json.loads(capsys.readouterr().out)
    assert reg["tool"] == "graftpass"
    assert {r["name"] for r in reg["registry"]} >= {
        "quantize_int8", "quantize_int4", "amp_bf16", "space_to_depth",
        "cse_dead_aux"}
    rc = gp.main(["--model", "dense",
                  "--passes", "quantize_int8,cse_dead_aux",
                  "--format", "json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert out["version"] == 1 and out["tool"] == "graftpass"
    assert out["summary"]["installed"] >= 1
    assert out["summary"]["errors"] == 0
    q = [p for p in out["passes"] if p["name"] == "quantize_int8"][0]
    assert q["installed"] and q["param_bytes_after"] \
        < q["param_bytes_before"]


def test_cli_exit_1_on_contract_violation(capsys):
    import tools.graftpass as gp

    register_pass("_test_cli_breaker", _ValueBreaker())
    try:
        with pytest.warns(UserWarning, match="GL301"):
            rc = gp.main(["--model", "dense",
                          "--passes", "_test_cli_breaker",
                          "--format", "json"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert out["summary"]["errors"] >= 1
        assert any(d["code"] == "GL301" for d in out["diagnostics"])
    finally:
        PASS_REGISTRY.pop("_test_cli_breaker", None)
    assert gp.main(["--model", "dense", "--passes", "no_such_pass"]) == 1
