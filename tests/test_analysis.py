"""graftlint Level 1 (trace-time) — adversarial fixtures for the five
seeded defect classes GL001–GL005, the eager call-site validators, and
the make_train_step(lint=...) wiring.

The headline acceptance: every defect class is detected on a minimal
repro, the existing production step paths (dp, dp×pp pipeline, MoE/ep)
report ZERO error-severity findings under ``lint="error"``, and the
lint trace runs once per step (pre-compile only)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, nd, tracing
from incubator_mxnet_tpu.analysis import (LintError, Severity,
                                          check_partition_spec,
                                          check_permutation,
                                          lint_traceable,
                                          validate_permutation)
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.parallel import P, make_mesh, make_train_step
from incubator_mxnet_tpu.parallel.mesh import shard_map

LOSS = gluon.loss.SoftmaxCrossEntropyLoss


def _mesh_dp_pp():
    return make_mesh({"dp": 2, "pp": 4})


# ---------------------------------------------------------------------------
# GL001 — permutation hygiene
# ---------------------------------------------------------------------------

def test_gl001_duplicate_and_oob_ranks():
    diags = check_permutation([(0, 1), (1, 2), (2, 1), (3, 0)], 4, "pp")
    assert any(d.code == "GL001" and d.severity == Severity.ERROR
               and "destination" in d.message for d in diags)
    diags = check_permutation([(0, 1), (0, 2)], 4, "pp")
    assert any("source" in d.message and d.severity == Severity.ERROR
               for d in diags)
    diags = check_permutation([(0, 5)], 4, "pp")
    assert any("out of range" in d.message for d in diags)


def test_gl001_partial_ring_is_info_not_error():
    """The pipeline fill/drain pattern (no wraparound) is informational:
    a ring missing its wraparound edge is reported, but not an error."""
    diags = check_permutation([(i, i + 1) for i in range(3)], 4, "pp")
    assert diags and all(d.severity == Severity.INFO for d in diags)
    assert "not bijective" in diags[0].message
    # the full ring is silent
    assert not check_permutation([(i, (i + 1) % 4) for i in range(4)],
                                 4, "pp")


def test_gl001_traced_bad_ring_detected():
    mesh = _mesh_dp_pp()

    def bad_ring(x):
        def body(xb):
            return lax.ppermute(xb, "pp",
                                [(0, 1), (1, 2), (2, 1), (3, 0)])
        return shard_map(body, mesh=mesh, in_specs=(P("pp"),),
                         out_specs=P("pp"))(x)

    report = lint_traceable(bad_ring, (jnp.ones(8),))
    assert [d.code for d in report.errors] == ["GL001"]


def test_gl001_eager_collectives_validation():
    """Satellite: collectives.ppermute raises eagerly at trace time,
    naming the axis and the duplicated ranks — instead of deadlocking
    or silently dropping a shard on hardware."""
    from incubator_mxnet_tpu.parallel.collectives import ppermute

    mesh = _mesh_dp_pp()

    def bad(x):
        def body(xb):
            return ppermute(xb, "pp", [(0, 1), (1, 2), (2, 1), (3, 0)])
        return shard_map(body, mesh=mesh, in_specs=(P("pp"),),
                         out_specs=P("pp"))(x)

    with pytest.raises(ValueError, match=r"GL001.*pp.*\[1\]"):
        jax.make_jaxpr(bad)(jnp.ones(8))

    def oob(x):
        def body(xb):
            return ppermute(xb, "pp", [(0, 7)])
        return shard_map(body, mesh=mesh, in_specs=(P("pp"),),
                         out_specs=P("pp"), check_vma=False)(x)

    with pytest.raises(ValueError, match="out of range"):
        jax.make_jaxpr(oob)(jnp.ones(8))


def test_validate_permutation_allows_partial():
    validate_permutation([(0, 1), (1, 2), (2, 3)], 4, "pp")  # fill/drain
    with pytest.raises(ValueError, match="duplicated source"):
        validate_permutation([(0, 1), (0, 2)], 4, "pp")


# ---------------------------------------------------------------------------
# GL002 — partition specs + the stacked-operand GSPMD hazard
# ---------------------------------------------------------------------------

def test_gl002_spec_rank_and_axis_names():
    mesh = _mesh_dp_pp()
    diags = check_partition_spec(("nope", None), 2, mesh)
    assert any(d.code == "GL002" and "does not exist" in d.message
               for d in diags)
    diags = check_partition_spec(("dp", None, None), 2, mesh)
    assert any("entries but" in d.message for d in diags)
    diags = check_partition_spec((0, None), 2, mesh)
    assert any("non-string" in d.message for d in diags)
    assert not check_partition_spec(("dp", None), 2, mesh)


def test_gl002_stacked_operand_hazard_minimal_repro():
    """Regression for the train_step.py stacked-operand GSPMD hazard:
    a jnp.stack built INSIDE the jitted program, fed to shard_map with
    a sharded in_spec on a multi-axis mesh, miscompiles on jax 0.4.x.
    graftlint must flag the repro as a GL002 error."""
    mesh = _mesh_dp_pp()

    def hazard(p1, p2, p3, p4, x):
        stacked = jnp.stack([p1, p2, p3, p4])

        def body(s, xb):
            return xb + s[0].sum()
        return shard_map(body, mesh=mesh, in_specs=(P("pp"), P()),
                         out_specs=P(), check_vma=False)(stacked, x)

    ps = [jnp.ones((3,)) for _ in range(4)]
    report = lint_traceable(hazard, (*ps, jnp.ones(8)))
    errs = report.by_code("GL002")
    assert errs and errs[0].severity == Severity.ERROR
    assert "stacked" in errs[0].message
    assert "axis_index" in errs[0].hint


def test_gl002_production_workaround_is_clean():
    """The replicated-in + axis_index-slice form used by
    TrainStep._make_pipeline_step must NOT be flagged."""
    mesh = _mesh_dp_pp()

    def clean(p1, p2, p3, p4, x):
        stacked = jnp.stack([p1, p2, p3, p4])

        def body(s, xb):
            i = lax.axis_index("pp")
            return xb + lax.dynamic_index_in_dim(
                s, i, keepdims=False).sum()
        return shard_map(body, mesh=mesh, in_specs=(P(), P()),
                         out_specs=P(), check_vma=False)(stacked, x)

    ps = [jnp.ones((3,)) for _ in range(4)]
    report = lint_traceable(clean, (*ps, jnp.ones(8)))
    assert not report.by_code("GL002")


def test_gl002_moe_sharded_eager_validation():
    from incubator_mxnet_tpu.parallel.moe import moe_ffn_sharded

    rng = np.random.RandomState(0)
    T, D, E, H = 8, 4, 4, 6
    args = (jnp.asarray(rng.normal(size=(T, D)).astype(np.float32)),
            jnp.asarray(rng.normal(size=(D, E)).astype(np.float32)),
            jnp.asarray(rng.normal(size=(E, D, H)).astype(np.float32)),
            jnp.asarray(np.zeros((E, H), np.float32)),
            jnp.asarray(rng.normal(size=(E, H, D)).astype(np.float32)),
            jnp.asarray(np.zeros((E, D), np.float32)))
    mesh = make_mesh({"ep": 4}, devices=jax.devices()[:4])
    with pytest.raises(LintError, match="GL002"):
        moe_ffn_sharded(*args, mesh, axis_name="nope")
    mesh3 = make_mesh({"ep": 3}, devices=jax.devices()[:3])
    with pytest.raises(ValueError, match="do not divide"):
        moe_ffn_sharded(*args, mesh3)


# ---------------------------------------------------------------------------
# GL003 — donation aliasing
# ---------------------------------------------------------------------------

def test_gl003_donated_buffer_aliased_twice():
    def alias(a, b):
        return a, a, a + b

    report = lint_traceable(alias, (jnp.ones(3), jnp.ones(3)),
                            donate_argnums=(0,))
    errs = report.by_code("GL003")
    assert errs and errs[0].severity == Severity.ERROR
    assert "2 distinct outputs" in errs[0].message


def test_gl003_wasted_donation_warns():
    def wasted(a, b):
        return (a[0] + b.sum(),)

    report = lint_traceable(wasted, (jnp.ones(3), jnp.ones(4)),
                            donate_argnums=(0,))
    diags = report.by_code("GL003")
    assert diags and diags[0].severity == Severity.WARNING
    assert "read-after-donate" in diags[0].message


def test_gl003_clean_functional_update():
    def ok(a, b):
        return a + b, b

    report = lint_traceable(ok, (jnp.ones(3), jnp.ones(3)),
                            donate_argnums=(0,))
    assert not report.by_code("GL003")


# ---------------------------------------------------------------------------
# GL004 — aux effects dropped by remat / inner trace regions
# ---------------------------------------------------------------------------

def test_gl004_aux_loss_under_raw_checkpoint_detected():
    def leaky(x):
        tc = tracing.TraceContext(None, training=True)
        tracing.push_trace(tc)
        try:
            def inner(y):
                tracing.current_trace().add_aux_loss((y * 2).sum())
                return y * 2
            out = jax.checkpoint(inner)(x)
            loss = out.sum()  # aux loss silently dropped
        finally:
            tracing.pop_trace()
        return loss

    report = lint_traceable(leaky, (jnp.ones(3),))
    errs = report.by_code("GL004")
    assert errs and errs[0].severity == Severity.ERROR
    assert "checkpoint" in errs[0].message


def test_gl004_lifted_aux_loss_is_clean():
    """The gluon/block.py _forward_remat discipline — lift effects out
    as checkpoint outputs, re-register outside — must not be flagged."""
    def lifted(x):
        tc = tracing.TraceContext(None, training=True)
        tracing.push_trace(tc)
        try:
            def inner(y):
                return y * 2, (y * 2).sum()
            out, al = jax.checkpoint(inner)(x)
            tracing.current_trace().add_aux_loss(al)
            loss = out.sum() + sum(tc.aux_losses)
        finally:
            tracing.pop_trace()
        return loss

    report = lint_traceable(lifted, (jnp.ones(3),))
    assert not report.by_code("GL004")


def test_gl004_moe_remat_block_is_clean():
    """MoEFFN inside hybridize(remat=True) lifts its aux loss through
    the checkpoint — the linted fused step must stay GL004-clean."""
    from incubator_mxnet_tpu.gluon.contrib.nn import MoEFFN

    mx.random.seed(5)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu"),
            MoEFFN(16, 4, top_k=2, aux_loss_weight=1e-2), nn.Dense(4))
    net.initialize(init=mx.init.Xavier())
    net(nd.ones((2, 8)))
    net.hybridize(remat=True)
    step = make_train_step(net, LOSS(), optimizer="sgd",
                           learning_rate=0.1, lint="error")
    rng = np.random.RandomState(0)
    x = nd.array(rng.rand(8, 8).astype(np.float32))
    y = nd.array((np.arange(8) % 4).astype(np.float32))
    assert np.isfinite(float(step(x, y).asscalar()))


def test_add_aux_loss_rejects_non_scalar():
    """Satellite: a vector aux loss corrupts the objective downstream —
    reject it at registration with shape and source in the message."""
    tc = tracing.TraceContext(None, training=True)
    with pytest.raises(ValueError, match=r"\(3,\)"):
        tc.add_aux_loss(jnp.ones(3))
    with pytest.raises(ValueError, match="MyBlock"):
        tc.add_aux_loss(jnp.ones((2, 2)), source="MyBlock")
    tc.add_aux_loss(jnp.float32(0.5))       # scalar array ok
    tc.add_aux_loss(0.25)                   # python scalar ok
    assert len(tc.aux_losses) == 2


# ---------------------------------------------------------------------------
# GL005 — recompile hazards
# ---------------------------------------------------------------------------

def test_gl005_host_scalar_argument():
    report = lint_traceable(lambda s: s * 2.0, (3.0,),
                            recompile_probe=True)
    diags = report.by_code("GL005")
    assert diags and "scalar" in diags[0].message


def test_gl005_nondeterministic_trace():
    def nondet(x):
        return x + np.random.rand(3)

    report = lint_traceable(nondet, (jnp.ones(3),), recompile_probe=True)
    assert any("different programs" in d.message
               for d in report.by_code("GL005"))


def test_gl005_deterministic_is_clean():
    report = lint_traceable(lambda x: x * 2 + 1, (jnp.ones(3),),
                            recompile_probe=True)
    assert not report.by_code("GL005")


# ---------------------------------------------------------------------------
# wiring: make_train_step(lint=...)
# ---------------------------------------------------------------------------

def _build_net(seed=3, feat=16, layers=4):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    for _ in range(layers):
        net.add(nn.Dense(feat, activation="tanh"))
    net.initialize(init=mx.init.Xavier())
    net(nd.ones((2, feat)))
    return net


def _batch(feat=16, batch=16):
    rng = np.random.RandomState(0)
    return (nd.array(rng.rand(batch, feat).astype(np.float32)),
            nd.array((np.arange(batch) % 4).astype(np.float32)))


@pytest.mark.parametrize("axes,pp", [(None, None), ({"dp": 8}, None),
                                     ({"dp": 2, "pp": 4}, 4)])
def test_train_step_paths_lint_clean_under_error(axes, pp):
    """Acceptance: the existing fused-step paths report zero
    error-severity findings — lint='error' must not raise."""
    x, y = _batch()
    mesh = make_mesh(axes) if axes else None
    step = make_train_step(_build_net(), LOSS(), optimizer="sgd",
                           learning_rate=0.1, mesh=mesh,
                           pipeline_stages=pp,
                           num_micro=4 if pp else 1, lint="error")
    loss = float(step(x, y).asscalar())
    assert np.isfinite(loss)
    assert step._linted


def test_train_step_lint_runs_once_pre_compile(monkeypatch):
    """The lint trace happens once, before the first compile; steady-
    state steps never re-enter the linter."""
    import incubator_mxnet_tpu.analysis as analysis

    calls = []
    real = analysis.lint_jaxpr

    def counting(*a, **k):
        calls.append(1)
        return real(*a, **k)

    monkeypatch.setattr(analysis, "lint_jaxpr", counting)
    x, y = _batch()
    step = make_train_step(_build_net(), LOSS(), optimizer="sgd",
                           learning_rate=0.1, lint="error")
    for _ in range(3):
        step(x, y)
    assert len(calls) == 1


def test_train_step_lint_error_reraises_on_retry(monkeypatch):
    """lint='error' keeps enforcing: a caught LintError followed by a
    retry must lint (and raise) again, never compile the flagged
    program silently."""
    import incubator_mxnet_tpu.analysis as analysis
    from incubator_mxnet_tpu.analysis import Diagnostic, LintReport

    def always_bad(*a, **k):
        return LintReport([Diagnostic("GL002", Severity.ERROR, "boom")])

    monkeypatch.setattr(analysis, "lint_jaxpr", always_bad)
    x, y = _batch()
    step = make_train_step(_build_net(), LOSS(), optimizer="sgd",
                           learning_rate=0.1, lint="error")
    for _ in range(2):
        with pytest.raises(LintError):
            step(x, y)
    assert not step._linted


def test_train_step_lint_off_skips(monkeypatch):
    import incubator_mxnet_tpu.analysis as analysis

    calls = []
    monkeypatch.setattr(analysis, "lint_jaxpr",
                        lambda *a, **k: calls.append(1))
    x, y = _batch()
    step = make_train_step(_build_net(), LOSS(), optimizer="sgd",
                           learning_rate=0.1, lint="off")
    step(x, y)
    assert not calls


def test_train_step_lint_env_default(monkeypatch):
    monkeypatch.setenv("MXTPU_LINT", "off")
    step = make_train_step(_build_net(), LOSS(), optimizer="sgd")
    assert step.lint == "off"
    monkeypatch.delenv("MXTPU_LINT")
    step = make_train_step(_build_net(), LOSS(), optimizer="sgd")
    assert step.lint == "warn"
    with pytest.raises(ValueError, match="lint"):
        make_train_step(_build_net(), LOSS(), optimizer="sgd",
                        lint="loud")


def test_lint_suppress_per_call():
    """docs/ANALYSIS.md suppression: suppressed codes drop out of the
    report but stay inspectable."""
    def alias(a, b):
        return a, a, a + b

    report = lint_traceable(alias, (jnp.ones(3), jnp.ones(3)),
                            donate_argnums=(0,), suppress=("GL003",))
    assert not report.by_code("GL003")
    assert any(d.code == "GL003" for d in report.suppressed)


# ---------------------------------------------------------------------------
# eager sharding-collective validation (reduce_scatter / allgather /
# alltoall — the PR-2 ppermute treatment)
# ---------------------------------------------------------------------------

def test_eager_reduce_scatter_divisibility():
    """reduce_scatter raises at trace time, naming the axis, when the
    scatter dimension does not divide the axis size — instead of a
    cryptic XLA shape error at compile."""
    from incubator_mxnet_tpu.parallel.collectives import reduce_scatter

    mesh = _mesh_dp_pp()

    def bad(x):
        def body(xb):
            return reduce_scatter(xb, "pp", scatter_dimension=0)
        return shard_map(body, mesh=mesh, in_specs=(P(),),
                         out_specs=P("pp"), check_vma=False)(x)

    with pytest.raises(ValueError, match=r"reduce_scatter over axis 'pp' "
                                         r"\(size 4\).*size 6.*not divide"):
        jax.make_jaxpr(bad)(jnp.ones(6))

    def bad_dim(x):
        def body(xb):
            return reduce_scatter(xb, "pp", scatter_dimension=2)
        return shard_map(body, mesh=mesh, in_specs=(P(),),
                         out_specs=P("pp"), check_vma=False)(x)

    with pytest.raises(ValueError, match="scatter 2 is out of range"):
        jax.make_jaxpr(bad_dim)(jnp.ones(8))


def test_eager_allgather_and_alltoall_validation():
    from incubator_mxnet_tpu.parallel.collectives import allgather, alltoall

    mesh = _mesh_dp_pp()

    def bad_gather(x):
        def body(xb):
            return allgather(xb, "pp", axis=3)
        return shard_map(body, mesh=mesh, in_specs=(P("pp"),),
                         out_specs=P("pp"), check_vma=False)(x)

    with pytest.raises(ValueError, match="allgather over axis 'pp'.*"
                                         "concat 3 is out of range"):
        jax.make_jaxpr(bad_gather)(jnp.ones(8))

    def bad_a2a(x):
        def body(xb):
            return alltoall(xb, "pp", split_axis=0, concat_axis=1)
        return shard_map(body, mesh=mesh, in_specs=(P(),),
                         out_specs=P("pp"), check_vma=False)(x)

    with pytest.raises(ValueError, match=r"alltoall over axis 'pp' "
                                         r"\(size 4\).*split dimension 0 "
                                         r"has size 6"):
        jax.make_jaxpr(bad_a2a)(jnp.ones((6, 2)))


# ---------------------------------------------------------------------------
# GL006 — defeated ZeRO sharding
# ---------------------------------------------------------------------------

def test_gl006_replicated_state_leaf_flagged():
    """An optimizer-state sharding left replicated over dp under zero=1
    is the N x memory the feature removes — ERROR, naming the axis."""
    from jax.sharding import NamedSharding
    from incubator_mxnet_tpu.analysis import check_zero_state_shardings

    mesh = _mesh_dp_pp()
    good = NamedSharding(mesh, P("dp"))
    bad = NamedSharding(mesh, P())
    diags = check_zero_state_shardings([good, (bad, good)], "dp")
    assert [d.code for d in diags] == ["GL006"]
    assert diags[0].severity == Severity.ERROR
    assert "replicated" in diags[0].message and "'dp'" in diags[0].message
    # sharded over the WRONG axis is also flagged (still replicated on dp)
    diags = check_zero_state_shardings([NamedSharding(mesh, P("pp"))], "dp")
    assert len(diags) == 1 and "sharded only over" in diags[0].message
    assert not check_zero_state_shardings([good, (good, good)], "dp")


def test_gl006_redundant_allgather_of_replicated_operand():
    """all_gather of an operand that enters the shard_map replicated
    (in_spec P()) multiplies a full buffer by the axis size — WARNING."""
    mesh = _mesh_dp_pp()

    def redundant(x):
        def body(xb):
            return lax.all_gather(xb, "dp", axis=0, tiled=True)
        return shard_map(body, mesh=mesh, in_specs=(P(),),
                         out_specs=P("dp"), check_vma=False)(x)

    report = lint_traceable(redundant, (jnp.ones(4),))
    hits = report.by_code("GL006")
    assert len(hits) == 1 and hits[0].severity == Severity.WARNING
    assert "already-full" in hits[0].message

    def legitimate(x):
        def body(xb):
            return lax.all_gather(xb, "dp", axis=0, tiled=True)
        return shard_map(body, mesh=mesh, in_specs=(P("dp"),),
                         out_specs=P("dp"), check_vma=False)(x)

    assert not lint_traceable(legitimate, (jnp.ones(4),)).by_code("GL006")


def test_gl006_zero_step_lints_clean_and_detects_regression():
    """The real zero=1 fused step passes lint="error" (its state IS
    dp-sharded), and the shardings it builds are GL006-clean."""
    from incubator_mxnet_tpu.analysis import check_zero_state_shardings

    mesh = make_mesh({"dp": 8})
    net = _build_net()
    step = make_train_step(net, LOSS(), optimizer="sgd", learning_rate=0.1,
                           momentum=0.9, mesh=mesh, zero=1, lint="error")
    x, y = _batch()
    assert np.isfinite(float(step(x, y).asscalar()))  # lint="error" passed
    # the shardings the step actually built are GL006-clean
    assert not check_zero_state_shardings(step._shardings[2], "dp")
