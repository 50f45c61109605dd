"""graftlint Level 2 (source/AST) + CLI gate.

Adversarial source fixtures for GL101/GL102/GL103, inline suppression,
and — the CI gate — ``tools/graftlint.py`` over the whole
``incubator_mxnet_tpu/`` package must exit 0: idiom violations fail
tier-1 from now on."""
import os
import sys
import textwrap

import pytest

from incubator_mxnet_tpu.analysis import Severity, lint_source
from incubator_mxnet_tpu.analysis.source_lint import lint_paths

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lint(src, path="pkg/mod.py"):
    return lint_source(textwrap.dedent(src), path=path)


# ---------------------------------------------------------------------------
# GL101 — shard_map import origin
# ---------------------------------------------------------------------------

def test_gl101_shard_map_from_jax_experimental():
    diags = _lint("""
        from jax.experimental.shard_map import shard_map
    """)
    assert [d.code for d in diags] == ["GL101"]
    assert "parallel.mesh" in diags[0].message


def test_gl101_shard_map_from_jax_toplevel():
    diags = _lint("""
        from jax import shard_map
    """)
    assert [d.code for d in diags] == ["GL101"]


def test_gl101_compat_home_exempt():
    src = """
        try:
            from jax import shard_map
        except ImportError:
            from jax.experimental.shard_map import shard_map
    """
    assert not _lint(src, path="incubator_mxnet_tpu/parallel/mesh.py")
    assert len(_lint(src, path="somewhere/else.py")) == 2


def test_gl101_importing_the_compat_home_is_clean():
    assert not _lint("""
        from incubator_mxnet_tpu.parallel.mesh import shard_map
        from .mesh import shard_map
    """)


# ---------------------------------------------------------------------------
# GL102 — side effects inside jit
# ---------------------------------------------------------------------------

def test_gl102_time_and_np_random_in_jit():
    diags = _lint("""
        import time
        import numpy as np
        import jax

        @jax.jit
        def step(x):
            t0 = time.time()
            noise = np.random.rand(4)
            return x + noise, t0
    """)
    assert sorted(d.code for d in diags) == ["GL102", "GL102"]
    assert all(d.severity == Severity.ERROR for d in diags)
    assert any("baked into" in d.message for d in diags)


def test_gl102_stdlib_random_but_not_jax_random():
    diags = _lint("""
        import random
        from functools import partial
        import jax

        @partial(jax.jit, static_argnums=0)
        def bad(n, x):
            return x * random.random()
    """)
    assert [d.code for d in diags] == ["GL102"]
    # `from jax import random` is NOT the stdlib PRNG — no finding
    assert not _lint("""
        import jax
        from jax import random

        @jax.jit
        def ok(key, x):
            return x + random.normal(key, x.shape)
    """)


def test_gl102_other_jits_not_flagged():
    """numba-style JITs allow host side effects — resolved through the
    import map, they must not match."""
    assert not _lint("""
        import time
        import numpy as np
        import numba
        from numba import jit

        @numba.jit
        def a(x):
            return np.random.rand(4) + time.time()

        @jit
        def b(x):
            return np.random.rand(4)
    """)


def test_gl102_only_inside_jit_decorated():
    assert not _lint("""
        import time
        import numpy as np

        def eager_benchmark(x):
            t0 = time.time()
            return np.random.rand(4), t0
    """)


# ---------------------------------------------------------------------------
# GL103 — PartitionSpec hygiene
# ---------------------------------------------------------------------------

def test_gl103_fstring_and_int_specs():
    diags = _lint("""
        from jax.sharding import PartitionSpec as P

        def make(ax):
            bad1 = P(f"{ax}")
            bad2 = P(0, None)
            ok = P("dp", None)
            return bad1, bad2, ok
    """)
    assert sorted(d.code for d in diags) == ["GL103", "GL103"]
    assert any("f-string" in d.message for d in diags)
    assert any("integer" in d.message for d in diags)


def test_gl103_attribute_path_partition_spec():
    """PartitionSpec reached through an attribute chain is checked too."""
    diags = _lint("""
        import jax

        def make(ax):
            return jax.sharding.PartitionSpec(f"{ax}")
    """)
    assert [d.code for d in diags] == ["GL103"]


def test_gl103_requires_spec_import_evidence():
    """An unrelated local function named P is not a PartitionSpec."""
    assert not _lint("""
        def P(x):
            return x

        y = P(f"hello")
    """)


# ---------------------------------------------------------------------------
# GL008 — checkpoint from a data loop without iterator state
# ---------------------------------------------------------------------------

def test_gl008_save_in_stateful_loop_without_data_iter():
    from incubator_mxnet_tpu.analysis import (
        CODES, check_checkpoint_without_iter_state)

    # cataloged (append-only contract, docs/ANALYSIS.md)
    assert CODES["GL008"][0] == Severity.WARNING
    src = """
        def train(step, train_iter, d):
            for batch in train_iter:
                step(batch.data[0], batch.label[0])
                step.save_checkpoint(d)
    """
    diags = _lint(src)
    assert [d.code for d in diags] == ["GL008"]
    assert diags[0].severity == Severity.WARNING
    assert "replays the epoch" in diags[0].message
    assert "data_iter" in diags[0].hint
    # the named core is directly callable on source text
    import textwrap

    direct = check_checkpoint_without_iter_state(textwrap.dedent(src))
    assert [d.code for d in direct] == ["GL008"]
    # attach_checkpoint inside the loop is the same hazard
    assert [d.code for d in _lint("""
        def train(step, loader, d):
            for i, batch in enumerate(loader):
                step.attach_checkpoint(d, every=100)
    """)] == ["GL008"]


def test_gl008_nested_stateful_loops_one_diagnostic_per_call():
    # ast.walk reaches the same call from BOTH enclosing stateful
    # loops — still exactly one diagnostic per save site
    diags = _lint("""
        def train(step, loader, loader2, d):
            for a in loader:
                for b in loader2:
                    step.save_checkpoint(d)
    """)
    assert [d.code for d in diags] == ["GL008"]


def test_gl008_clean_patterns():
    # data_iter= passed -> clean
    assert not _lint("""
        def train(step, train_iter, d):
            for batch in train_iter:
                step.save_checkpoint(d, data_iter=train_iter)
    """)
    # position-free iterables (literals, range) -> clean; call outside
    # any loop -> clean
    assert not _lint("""
        def train(step, d, batches):
            for batch in [1, 2, 3]:
                step.save_checkpoint(d)
            for i in range(10):
                step.attach_checkpoint(d)
            step.save_checkpoint(d)
    """)
    # inline suppression works for GL008 too
    assert not _lint("""
        def train(step, loader, d):
            for batch in loader:
                step.save_checkpoint(d)  # graftlint: disable=GL008
    """)


# ---------------------------------------------------------------------------
# GL009 — process-local checkpoint dir in a jax.distributed world
# ---------------------------------------------------------------------------

def test_gl009_process_local_ckpt_dir():
    import tempfile

    from incubator_mxnet_tpu.analysis import (
        CODES, check_process_local_ckpt_dir)

    assert CODES["GL009"][0] == Severity.WARNING
    tmp = tempfile.gettempdir()
    diags = check_process_local_ckpt_dir(os.path.join(tmp, "ckpts"), 4)
    assert [d.code for d in diags] == ["GL009"]
    assert diags[0].severity == Severity.WARNING
    assert "4 processes" in diags[0].message
    assert "shared filesystem" in diags[0].hint
    # relative paths resolve per-process working dirs: flagged too
    assert [d.code for d in check_process_local_ckpt_dir("ckpts", 2)] \
        == ["GL009"]
    # a shared absolute path is clean; so is any dir at world size 1
    assert check_process_local_ckpt_dir("/shared/nfs/ckpts", 4) == []
    assert check_process_local_ckpt_dir(os.path.join(tmp, "c"), 1) == []


def test_gl009_fires_at_manager_construction(tmp_path):
    """The one wired emission point: constructing a CheckpointManager
    with process_count > 1 over a process-local directory warns with
    the GL009 diagnostic; a single-process manager never does."""
    import warnings as _w

    from incubator_mxnet_tpu.parallel import CheckpointManager

    with pytest.warns(UserWarning, match="GL009"):
        CheckpointManager(str(tmp_path / "c"), process_index=0,
                          process_count=2)
    with _w.catch_warnings():
        _w.simplefilter("error")
        CheckpointManager(str(tmp_path / "c"), process_count=1)


def test_inline_suppression():
    diags = _lint("""
        from jax import shard_map  # graftlint: disable=GL101
    """)
    assert not diags
    diags = _lint("""
        from jax import shard_map  # graftlint: disable
    """)
    assert not diags
    diags = _lint("""
        from jax import shard_map  # graftlint: disable=GL102
    """)
    assert [d.code for d in diags] == ["GL101"]


# ---------------------------------------------------------------------------
# the CI gate
# ---------------------------------------------------------------------------

def test_repo_package_is_idiom_clean():
    """Level 2 over the real package: zero findings of any severity.
    New code that imports shard_map from jax, calls time/np.random
    inside jit, or builds f-string specs fails tier-1 here."""
    report = lint_paths([os.path.join(ROOT, "incubator_mxnet_tpu")])
    assert not report.errors, "\n" + report.format()
    assert not report.warnings, "\n" + report.format()


def test_cli_exit_codes(tmp_path):
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import graftlint
    finally:
        sys.path.pop(0)
    # clean package -> 0
    assert graftlint.main([os.path.join(ROOT, "incubator_mxnet_tpu",
                                        "analysis")]) == 0
    # a violating file -> 1
    bad = tmp_path / "bad.py"
    bad.write_text("from jax.experimental.shard_map import shard_map\n")
    assert graftlint.main([str(tmp_path)]) == 1
    # suppressed -> 0
    assert graftlint.main([str(tmp_path), "--suppress", "GL101"]) == 0


def test_cli_select_ignore_filters(tmp_path):
    """--select/--ignore code filters: CI can gate on a precise code set
    while other codes stay advisory; ignored codes drop from the exit
    status too."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import graftlint
    finally:
        sys.path.pop(0)
    bad = tmp_path / "bad.py"
    bad.write_text("from jax import shard_map\n"
                   "from jax.sharding import PartitionSpec as P\n"
                   "s = P(0)\n")  # GL101 + GL103
    # unfiltered: both errors gate
    assert graftlint.main([str(tmp_path)]) == 1
    # select only GL103 -> still 1 (GL103 is an error); GL101 dropped
    assert graftlint.main([str(tmp_path), "--select", "GL103"]) == 1
    # ignore both -> clean exit
    assert graftlint.main([str(tmp_path), "--ignore", "GL101,GL103"]) == 0
    # select a code the file does not violate -> clean exit
    assert graftlint.main([str(tmp_path), "--select", "GL102"]) == 0
    # --suppress stays an alias of --ignore
    assert graftlint.main([str(tmp_path), "--suppress", "GL101",
                           "--ignore", "GL103"]) == 0


def test_cli_gate_over_package_with_select():
    """Tier-1 wiring: the CLI gates the real package on the GL10x error
    codes (the invocation CI runs)."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import graftlint
    finally:
        sys.path.pop(0)
    assert graftlint.main([os.path.join(ROOT, "incubator_mxnet_tpu"),
                           "--select", "GL101,GL102,GL103"]) == 0


def test_gl007_legacy_save_states_from_zero1_fused_trainer(tmp_path):
    """GL007 gate: a zero=1 fused step built from a Trainer warns that
    the legacy save_states path is still reachable (it cannot round-trip
    dp-sharded optimizer state), and the Trainer raises if it IS called
    — pointing at the shard-aware checkpoint API."""
    import warnings

    import numpy as np

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, nd
    from incubator_mxnet_tpu.analysis import (CODES, Severity as Sev,
                                              check_legacy_checkpoint_path)
    from incubator_mxnet_tpu.gluon import nn
    from incubator_mxnet_tpu.parallel import make_mesh

    # the code is cataloged (append-only contract, docs/ANALYSIS.md)
    assert CODES["GL007"][0] == Sev.WARNING
    diags = check_legacy_checkpoint_path("Trainer", where="here")
    assert [d.code for d in diags] == ["GL007"]
    assert "save_states" in diags[0].message
    assert "checkpoint" in diags[0].hint

    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="tanh"), nn.Dense(8))
    net.initialize(init=mx.init.Xavier())
    net(nd.ones((2, 8)))
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    step = trainer.make_fused_step(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                                   mesh=make_mesh({"dp": 8}), zero=1,
                                   lint="warn")
    x = nd.array(np.random.RandomState(0).rand(8, 8).astype(np.float32))
    y = nd.array((np.arange(8) % 4).astype(np.float32))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        step(x, y)
    assert any("GL007" in str(w.message) for w in caught), \
        [str(w.message) for w in caught]
    with pytest.raises(RuntimeError, match="save_checkpoint"):
        trainer.save_states(str(tmp_path / "should_not_exist.states"))
    with pytest.raises(RuntimeError, match="restore_checkpoint"):
        trainer.load_states(str(tmp_path / "should_not_exist.states"))
    # a plain (zero=0) fused-step Trainer keeps the legacy path
    trainer2 = gluon.Trainer(net.collect_params(), "sgd",
                             {"learning_rate": 0.1})
    trainer2.make_fused_step(net, gluon.loss.SoftmaxCrossEntropyLoss())
    trainer2.save_states(str(tmp_path / "gl007_plain.states"))
    assert os.path.exists(tmp_path / "gl007_plain.states")


def test_gl012_unbounded_silent_skip_streak():
    """GL012 gate: nonfinite='skip' under a STATIC loss scale with no
    skip-streak bound warns (an unbounded silent skip-streak is a
    stalled run that looks alive); a dynamic scale or a declared
    skip_streak_budget silences it.  The live enforcement — the
    supervisor's divergence verdict at the declared budget — lives in
    tests/test_supervisor.py."""
    import warnings

    import numpy as np

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, nd
    from incubator_mxnet_tpu.analysis import (CODES, Severity as Sev,
                                              check_unbounded_skip)
    from incubator_mxnet_tpu.gluon import nn
    from incubator_mxnet_tpu.parallel import make_train_step

    # the code is cataloged (append-only contract, docs/ANALYSIS.md)
    assert CODES["GL012"][0] == Sev.WARNING
    diags = check_unbounded_skip("skip", False, None, where="here")
    assert [d.code for d in diags] == ["GL012"]
    assert "static loss scale" in diags[0].message
    assert "dynamic" in diags[0].hint and \
        "skip_streak_budget" in diags[0].hint
    # every bounded configuration is clean
    assert check_unbounded_skip("skip", True, None) == []     # dynamic
    assert check_unbounded_skip("skip", False, 16) == []      # budget
    assert check_unbounded_skip("raise", False, None) == []   # loud
    assert check_unbounded_skip("off", False, None) == []

    def build(**kw):
        mx.random.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Dense(8, activation="tanh"), nn.Dense(4))
        net.initialize(init=mx.init.Xavier())
        net(nd.ones((2, 8)))
        return make_train_step(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                               optimizer="sgd", learning_rate=0.1,
                               lint="warn", **kw)

    x = nd.array(np.random.RandomState(0).rand(4, 8).astype(np.float32))
    y = nd.array((np.arange(4) % 4).astype(np.float32))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        build(nonfinite="skip", loss_scale=1024.0)(x, y)
    assert any("GL012" in str(w.message) for w in caught), \
        [str(w.message) for w in caught]
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        build(nonfinite="skip", loss_scale=1024.0,
              skip_streak_budget=8)(x, y)
    assert not any("GL012" in str(w.message) for w in caught), \
        [str(w.message) for w in caught]


def test_gl010_inference_param_donation():
    """GL010 gate: the check names overlapping param leaves as an
    error; disjoint donation (cache/input argnums) is clean.  The
    engine-level integration — ``ServeEngine(donate_argnums=(0,))``
    refused at trace time — lives in tests/test_serve.py."""
    from incubator_mxnet_tpu.analysis import (
        CODES, Severity as Sev, check_inference_param_donation)

    # the code is cataloged (append-only contract, docs/ANALYSIS.md)
    assert CODES["GL010"][0] == Sev.ERROR
    diags = check_inference_param_donation([0, 1, 5], range(4),
                                           where="ServeEngine(net)")
    assert [d.code for d in diags] == ["GL010"]
    assert diags[0].severity == Sev.ERROR
    assert "[0, 1]" in diags[0].message
    assert "decode cache" in diags[0].hint
    # donated per-request state outside the param leaves is the
    # LEGITIMATE pattern (serve/cache.py donates the cache argnum)
    assert check_inference_param_donation([5, 6], range(4)) == []
    assert check_inference_param_donation([], range(4)) == []


def test_gl011_swap_compatibility():
    """GL011 gate: shape/dtype/tree drift between the served param
    signature and a hot-swap candidate is an aggregated error; an
    identical candidate is clean.  The engine-level integration —
    ``ServeEngine.update_params`` refusing a drifted candidate before
    staging anything — lives in tests/test_serve_resilience.py."""
    import numpy as np

    from incubator_mxnet_tpu.analysis import (
        CODES, Severity as Sev, check_swap_compatibility)

    # the code is cataloged (append-only contract, docs/ANALYSIS.md)
    assert CODES["GL011"][0] == Sev.ERROR
    served = [("w", (4, 4), np.dtype(np.float32)),
              ("b", (4,), np.dtype(np.float32))]
    # identical candidate: clean
    assert check_swap_compatibility(served, list(served)) == []
    # shape + dtype drift: ONE aggregated error naming both
    cand = [("w", (4, 5), np.dtype(np.float32)),
            ("b", (4,), np.dtype(np.float64))]
    diags = check_swap_compatibility(served, cand, where="update_params")
    assert [d.code for d in diags] == ["GL011"]
    assert diags[0].severity == Sev.ERROR
    assert "shape (4, 4) -> (4, 5)" in diags[0].message
    assert "dtype float32 -> float64" in diags[0].message
    assert "recompile" in diags[0].message
    assert "param_signature" in diags[0].hint
    # tree drift: missing + foreign names
    diags = check_swap_compatibility(served, list(served),
                                     missing=("b",), extra=("c",))
    assert len(diags) == 1 and "missing from candidate" in diags[0].message
    assert "not in the served tree" in diags[0].message
    # tree drift: raw length mismatch is NEVER zip-truncated to clean
    diags = check_swap_compatibility(served, served[:1])
    assert len(diags) == 1 and "param count 2 -> 1" in diags[0].message


def test_gl014_ungated_promotion_swap_runtime():
    """GL014 gate (runtime sightline): a self-identified promotion/
    daemon swap (``context=``) with neither canary rows nor a
    ``canary_tol`` warns — the only gate left is the zeros canary's
    finiteness check, which a finite-but-wrong candidate passes.  Any
    declared gate, or an interactive (context-free) swap, is clean."""
    import warnings

    import numpy as np

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd
    from incubator_mxnet_tpu.analysis import (CODES, Severity as Sev,
                                              check_ungated_swap)
    from incubator_mxnet_tpu.gluon import nn
    from incubator_mxnet_tpu.serve import ServeEngine

    # the code is cataloged (append-only contract, docs/ANALYSIS.md)
    assert CODES["GL014"][0] == Sev.WARNING
    diags = check_ungated_swap(None, None, context="promotion",
                               where="here")
    assert [d.code for d in diags] == ["GL014"]
    assert "promotion" in diags[0].message
    assert "canary" in diags[0].hint
    # any declared gate, or no daemon context, is clean
    assert check_ungated_swap(np.zeros((1, 4)), None,
                              context="promotion") == []
    assert check_ungated_swap(None, 0.5, context="promotion") == []
    assert check_ungated_swap(None, None, context=None) == []

    def build(**kw):
        mx.random.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Dense(8, activation="tanh"), nn.Dense(4))
        net.initialize(init=mx.init.Xavier())
        net(nd.ones((2, 8)))
        eng = ServeEngine(net, buckets=(4,), lint="warn", **kw)
        eng.warmup(np.zeros((8,), np.float32))
        return eng

    eng = build()
    cand = [np.array(p._data._data) for p in eng._params]
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        eng.update_params([np.array(a) for a in cand], context="daemon")
    assert any("GL014" in str(w.message) for w in caught), \
        [str(w.message) for w in caught]
    # gated daemon swap: no warning
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        eng.update_params([np.array(a) for a in cand], canary_tol=10.0,
                          context="daemon")
    assert not any("GL014" in str(w.message) for w in caught)
    # suppression is honored
    eng2 = build(lint_suppress=("GL014",))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        eng2.update_params([np.array(a) for a in cand],
                           context="daemon")
    assert not any("GL014" in str(w.message) for w in caught)


def test_gl014_source_rule_promotion_name_stack():
    """GL014 gate (source sightline): a bare ``update_params(...)``
    inside a def/class whose name smells like a promotion/daemon path
    is flagged; passing either canary gate — or living outside such a
    scope — is clean, and inline suppression works."""
    from incubator_mxnet_tpu.analysis import check_promotion_swap_ungated

    flagged = _lint("""
        class PromotionDaemon:
            def evaluate(self, engine, raw):
                engine.update_params(raw)
    """)
    assert [d.code for d in flagged] == ["GL014"]
    assert "PromotionDaemon.evaluate" in flagged[0].message
    # either gate kwarg bound to a non-None value is gated
    assert _lint("""
        def flywheel_tick(engine, raw, rows):
            engine.update_params(raw, canary=rows)
    """) == []
    assert _lint("""
        def daemon_poll(engine, raw):
            engine.update_params(raw, canary_tol=4.0)
    """) == []
    # a positional canary and opaque **kwargs both count as gated
    assert _lint("""
        def promote(engine, raw, rows):
            engine.update_params(raw, rows)
    """) == []
    assert _lint("""
        def promote(engine, raw, **kw):
            engine.update_params(raw, **kw)
    """) == []
    # canary=None explicitly is NOT a gate
    assert [d.code for d in _lint("""
        def promote(engine, raw):
            engine.update_params(raw, canary=None)
    """)] == ["GL014"]
    # outside a promotion-scented scope: clean (interactive swap)
    assert _lint("""
        def handle_reload(engine, raw):
            engine.update_params(raw)
    """) == []
    # inline suppression
    assert _lint("""
        def promote(engine, raw):
            engine.update_params(raw)  # graftlint: disable=GL014
    """) == []
    # the standalone checker agrees with the lint_source integration
    diags = check_promotion_swap_ungated(
        "class Promoter:\n"
        "    def run(self, e, raw):\n"
        "        e.update_params(raw)\n", path="fly.py")
    assert [d.code for d in diags] == ["GL014"]
    assert diags[0].where == "fly.py:3"


def test_cli_reports_with_location(tmp_path, capsys):
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import graftlint
    finally:
        sys.path.pop(0)
    bad = tmp_path / "bad.py"
    bad.write_text("import jax\nfrom jax import shard_map\n")
    rc = graftlint.main([str(bad)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "bad.py:2" in out and "GL101" in out


# ---------------------------------------------------------------------------
# --format=json + prefix globs (stable machine schema for CI/autotuner)
# ---------------------------------------------------------------------------

def _tools_import(name):
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        return __import__(name)
    finally:
        sys.path.pop(0)


def test_cli_json_format_stable_schema(tmp_path, capsys):
    import json

    graftlint = _tools_import("graftlint")
    bad = tmp_path / "bad.py"
    bad.write_text("from jax import shard_map\n"
                   "from jax.sharding import PartitionSpec as P\n"
                   "s = P(0)\n")  # GL101 + GL103
    rc = graftlint.main([str(bad), "--format", "json"])
    obj = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert obj["version"] == 1 and obj["tool"] == "graftlint"
    assert obj["summary"]["errors"] == 2 and obj["summary"]["total"] == 2
    codes = sorted(f["code"] for f in obj["findings"])
    assert codes == ["GL101", "GL103"]
    for f in obj["findings"]:
        # the stable Diagnostic schema: severity serialized by NAME
        assert set(f) == {"code", "severity", "message", "where", "hint"}
        assert f["severity"] == "error"
        assert "bad.py" in f["where"]
    # clean run: empty findings, exit 0, still valid JSON
    rc = graftlint.main([os.path.join(ROOT, "incubator_mxnet_tpu",
                                      "analysis"), "--format", "json"])
    obj = json.loads(capsys.readouterr().out)
    assert rc == 0 and obj["findings"] == []


def test_cli_select_ignore_prefix_globs(tmp_path):
    graftlint = _tools_import("graftlint")
    bad = tmp_path / "bad.py"
    bad.write_text("from jax import shard_map\n"
                   "from jax.sharding import PartitionSpec as P\n"
                   "s = P(0)\n")  # GL101 + GL103
    # GL1* selects both -> still errors
    assert graftlint.main([str(bad), "--select", "GL1*"]) == 1
    # GL2* selects neither -> clean
    assert graftlint.main([str(bad), "--select", "GL2*"]) == 0
    # ignoring the whole GL1xx family silences the gate
    assert graftlint.main([str(bad), "--ignore", "GL1*"]) == 0
    # --suppress alias takes globs too
    assert graftlint.main([str(bad), "--suppress", "GL10*"]) == 0


def test_lint_suppress_accepts_globs():
    """make_train_step(lint_suppress=("GL2*",)) and LintReport share
    the same glob grammar as the CLI filters."""
    from incubator_mxnet_tpu.analysis import (Diagnostic, LintReport,
                                              Severity as Sev)

    rep = LintReport(suppress=("GL00?", "GL2*"))
    rep.add(Diagnostic("GL002", Sev.ERROR, "a"))
    rep.add(Diagnostic("GL203", Sev.WARNING, "b"))
    rep.add(Diagnostic("GL101", Sev.ERROR, "c"))
    assert [d.code for d in rep] == ["GL101"]
    assert sorted(d.code for d in rep.suppressed) == ["GL002", "GL203"]


# ---------------------------------------------------------------------------
# graftcost CLI gate (CI: feasible -> 0, infeasible budget -> 1, JSON
# parses against the schema)
# ---------------------------------------------------------------------------

def test_graftcost_cli_gate_and_json(capsys):
    import json

    graftcost = _tools_import("graftcost")
    # feasible: the dense test net fits any real device -> exit 0
    assert graftcost.main(["--model", "dense", "--batch", "16"]) == 0
    capsys.readouterr()
    # infeasible --hbm-budget: GL201 -> exit 1
    assert graftcost.main(["--model", "dense", "--batch", "16",
                           "--hbm-budget", "1KiB"]) == 1
    out = capsys.readouterr().out
    assert "GL201" in out
    # JSON output parses against the CostReport schema
    assert graftcost.main(["--model", "dense", "--batch", "16",
                           "--format", "json"]) == 0
    obj = json.loads(capsys.readouterr().out)
    assert obj["version"] == 1
    for key in ("device", "categories", "totals", "peak_bytes",
                "opt_state_bytes", "comm", "roofline", "diagnostics"):
        assert key in obj, key
    assert obj["totals"]["hbm_bytes"] > 0
    assert obj["categories"]["conv"]["flops"] > 0
    assert set(obj["roofline"]) == {"compute_s", "hbm_s", "comm_s",
                                    "step_s"}
    # diagnostics ride the same stable Diagnostic schema
    assert graftcost.main(["--model", "dense", "--batch", "16",
                           "--hbm-budget", "1KiB", "--format",
                           "json"]) == 1
    obj = json.loads(capsys.readouterr().out)
    codes = [d["code"] for d in obj["diagnostics"]]
    assert "GL201" in codes
    for d in obj["diagnostics"]:
        assert set(d) == {"code", "severity", "message", "where", "hint"}


# ---------------------------------------------------------------------------
# --format=sarif (SARIF 2.1.0 for CI code-scanning UIs)
# ---------------------------------------------------------------------------

def _validate_sarif_2_1_0(log):
    """Structural validation against the SARIF 2.1.0 schema's required
    shape (no jsonschema dependency in the image: the invariants below
    ARE the schema's required properties for log/run/tool/driver/
    result/location objects)."""
    assert set(log) >= {"version", "runs"}
    assert log["version"] == "2.1.0"
    assert "sarif-schema-2.1.0" in log.get("$schema", "")
    assert isinstance(log["runs"], list) and log["runs"]
    for run in log["runs"]:
        assert "tool" in run and "driver" in run["tool"]
        driver = run["tool"]["driver"]
        assert isinstance(driver.get("name"), str) and driver["name"]
        rules = driver.get("rules", [])
        rule_ids = []
        for rule in rules:
            assert isinstance(rule["id"], str)
            assert "text" in rule.get("shortDescription", {})
            assert rule.get("defaultConfiguration", {}).get("level") \
                in ("none", "note", "warning", "error")
            rule_ids.append(rule["id"])
        for res in run.get("results", []):
            assert isinstance(res["message"]["text"], str) \
                and res["message"]["text"]
            assert res.get("level") in ("none", "note", "warning",
                                        "error")
            assert res["ruleId"] in rule_ids
            assert rules[res["ruleIndex"]]["id"] == res["ruleId"]
            for loc in res.get("locations", []):
                phys = loc["physicalLocation"]
                assert isinstance(
                    phys["artifactLocation"]["uri"], str)
                assert phys["region"]["startLine"] >= 1


def test_cli_sarif_format(tmp_path, capsys):
    import json

    graftlint = _tools_import("graftlint")
    bad = tmp_path / "bad.py"
    bad.write_text("from jax import shard_map\n"
                   "from jax.sharding import PartitionSpec as P\n"
                   "s = P(0)\n")  # GL101 + GL103
    rc = graftlint.main([str(bad), "--format", "sarif"])
    log = json.loads(capsys.readouterr().out)
    assert rc == 1
    _validate_sarif_2_1_0(log)
    results = log["runs"][0]["results"]
    assert sorted(r["ruleId"] for r in results) == ["GL101", "GL103"]
    assert all(r["level"] == "error" for r in results)
    # source findings carry a physical location with the right line
    gl101 = next(r for r in results if r["ruleId"] == "GL101")
    phys = gl101["locations"][0]["physicalLocation"]
    assert phys["artifactLocation"]["uri"].endswith("bad.py")
    assert phys["region"]["startLine"] == 1
    # rules metadata comes from the stable catalog
    rules = {r["id"]: r for r in log["runs"][0]["tool"]["driver"]["rules"]}
    assert "shard_map" in rules["GL101"]["shortDescription"]["text"]
    # a clean run is a valid SARIF log with zero results, exit 0
    rc = graftlint.main([os.path.join(ROOT, "incubator_mxnet_tpu",
                                      "analysis"), "--format", "sarif"])
    log = json.loads(capsys.readouterr().out)
    assert rc == 0
    _validate_sarif_2_1_0(log)
    assert log["runs"][0]["results"] == []


# ---------------------------------------------------------------------------
# GL304 — zero-site pass composition (graftsched, docs/ANALYSIS.md)
# ---------------------------------------------------------------------------

def test_gl304_cataloged():
    from incubator_mxnet_tpu.analysis import CODES

    sev, text = CODES["GL304"]
    assert sev == Severity.WARNING
    assert "zero sites" in text


def test_gl304_fires_on_zero_site_pass():
    """A pass named in passes= that matches nothing in the program is a
    silent no-op — GL304 warns; an explicitly schedule-disabled pass is
    a deliberate decision and stays quiet."""
    import warnings

    import numpy as np

    import jax

    from incubator_mxnet_tpu.analysis.passes import (PassContext,
                                                     PassManager,
                                                     PassSchedule)

    cj = jax.make_jaxpr(lambda a, b: a @ b)(
        jax.ShapeDtypeStruct((8, 8), np.float32),
        jax.ShapeDtypeStruct((8, 8), np.float32))
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        res = PassManager(["space_to_depth"],
                          raise_on_error=False).run(cj, PassContext())
    assert any(d.code == "GL304" for d in res.diagnostics)
    assert any("GL304" in str(x.message) for x in w)
    assert not res.receipts[0].installed  # still a clean no-op
    # disabled-by-schedule: no GL304 (the decision is on the record)
    sched = PassSchedule([("space_to_depth", False)])
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        res = PassManager(None, schedule=sched,
                          raise_on_error=False).run(cj, PassContext())
    assert not any(d.code == "GL304" for d in res.diagnostics)
    assert "disabled by schedule" in (res.receipts[0].notes or "")


def test_gl304_rides_graftpass_cli_without_gating(capsys):
    """GL304 is a WARNING: it lands in the CLI diagnostics but never
    flips the exit code."""
    import json

    import pytest as _pytest

    import tools.graftpass as gp

    with _pytest.warns(UserWarning, match="GL304"):
        rc = gp.main(["--model", "dense", "--passes", "space_to_depth",
                      "--format", "json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert any(d["code"] == "GL304" for d in out["diagnostics"])


# ---------------------------------------------------------------------------
# graftpass --schedule / --list-sites / --format sarif (graftsched CLI)
# ---------------------------------------------------------------------------

def test_graftpass_cli_list_sites(capsys):
    import json

    import tools.graftpass as gp

    rc = gp.main(["--model", "dense",
                  "--passes", "amp_bf16,quantize_int8,cse_dead_aux",
                  "--list-sites", "--format", "json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0
    by_pass = {}
    for r in out["sites"]:
        by_pass.setdefault(r["pass"], []).append(r)
    assert [r["site"] for r in by_pass["amp_bf16"]] == ["dot_general:0",
                                                        "dot_general:1"]
    assert all(r["site"].startswith("invar:")
               for r in by_pass["quantize_int8"])
    # whole-program passes report exactly that, not an empty listing
    assert by_pass["cse_dead_aux"][0]["site"] is None
    assert by_pass["cse_dead_aux"][0]["kind"] == "whole-program"


def test_graftpass_cli_schedule_decisions_and_receipts(tmp_path, capsys):
    import json

    import tools.graftpass as gp
    from incubator_mxnet_tpu.analysis.passes import PassSchedule

    sched = PassSchedule([("amp_bf16", {"dot_general:0": True,
                                        "dot_general:1": False})])
    f = tmp_path / "sched.json"
    f.write_text(sched.to_json())
    rc = gp.main(["--model", "dense", "--schedule", str(f),
                  "--format", "json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert out["schedule"]["hash"] == sched.hash()
    (amp,) = out["passes"]
    rows = {r["site"]: r for r in amp["sites"]}
    assert rows["dot_general:0"]["decision"] is True
    assert rows["dot_general:0"]["installed"] is True
    assert rows["dot_general:1"]["decision"] is False
    assert rows["dot_general:1"]["installed"] is False
    # a malformed schedule file is a usage error, not a crash
    bad = tmp_path / "bad.json"
    bad.write_text("{\"nope\": 1}")
    assert gp.main(["--model", "dense", "--schedule", str(bad)]) == 2


def test_graftpass_cli_schedule_exit_1_on_refused_site(tmp_path, capsys):
    """A schedule enabling a GL301-refused rewrite exits 1 — the CI
    gate shape."""
    import json

    import pytest as _pytest

    import tools.graftpass as gp
    from incubator_mxnet_tpu.analysis.passes import (PASS_REGISTRY,
                                                     PassSchedule,
                                                     register_pass)
    from tests.test_passes import _ValueBreaker

    register_pass("_test_sched_breaker", _ValueBreaker())
    try:
        f = tmp_path / "sched.json"
        f.write_text(PassSchedule(
            [("_test_sched_breaker", True)]).to_json())
        with _pytest.warns(UserWarning, match="GL301"):
            rc = gp.main(["--model", "dense", "--schedule", str(f),
                          "--format", "json"])
        out = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert any(d["code"] == "GL301" for d in out["diagnostics"])
    finally:
        PASS_REGISTRY.pop("_test_sched_breaker", None)


def test_graftpass_cli_sarif_format(capsys):
    import json

    import pytest as _pytest

    import tools.graftpass as gp

    with _pytest.warns(UserWarning, match="GL304"):
        rc = gp.main(["--model", "dense", "--passes", "space_to_depth",
                      "--format", "sarif"])
    log = json.loads(capsys.readouterr().out)
    assert rc == 0
    _validate_sarif_2_1_0(log)
    results = log["runs"][0]["results"]
    assert any(r["ruleId"] == "GL304" and r["level"] == "warning"
               for r in results)


def test_gl013_unsaved_compressor_residual():
    """GL013 gate: error-feedback compression on a sync='allreduce'
    step warns (the residual can never reach the checkpoint save set,
    so kill-and-resume silently drops the bank); the async rungs —
    whose param_service checkpoint subtree carries the compressor's
    state — are clean, as is no compression at all.  The resume-path
    enforcement (bit-identical tail through CheckpointManager) lives in
    tests/test_param_service.py."""
    import warnings

    import numpy as np

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, nd
    from incubator_mxnet_tpu.analysis import (CODES, Severity as Sev,
                                              check_unsaved_compressor_state)
    from incubator_mxnet_tpu.gluon import nn
    from incubator_mxnet_tpu.kvstore.gradient_compression import (
        Int8Compressor, make_compressor)
    from incubator_mxnet_tpu.parallel import make_train_step

    # the code is cataloged (append-only contract, docs/ANALYSIS.md)
    assert CODES["GL013"][0] == Sev.WARNING
    comp = make_compressor("topk")
    diags = check_unsaved_compressor_state(comp, "allreduce", where="here")
    assert [d.code for d in diags] == ["GL013"]
    assert "'topk'" in diags[0].message
    assert "sync='async'" in diags[0].hint
    # every safe configuration is clean
    assert check_unsaved_compressor_state(None, "allreduce") == []
    assert check_unsaved_compressor_state(comp, "async") == []
    assert check_unsaved_compressor_state(comp, "auto") == []
    assert check_unsaved_compressor_state(Int8Compressor(), "auto") == []

    def build(**kw):
        mx.random.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Dense(8, activation="tanh"), nn.Dense(4))
        net.initialize(init=mx.init.Xavier())
        net(nd.ones((2, 8)))
        return make_train_step(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                               optimizer="sgd", learning_rate=0.1,
                               lint="warn", **kw)

    x = nd.array(np.random.RandomState(0).rand(4, 8).astype(np.float32))
    y = nd.array((np.arange(4) % 4).astype(np.float32))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        build(compression="int8")(x, y)
    assert any("GL013" in str(w.message) for w in caught), \
        [str(w.message) for w in caught]
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        build(compression="int8", sync="async")(x, y)
    assert not any("GL013" in str(w.message) for w in caught), \
        [str(w.message) for w in caught]
    # lint_suppress opts out, like every other code
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        build(compression="int8", lint_suppress=("GL013",))(x, y)
    assert not any("GL013" in str(w.message) for w in caught), \
        [str(w.message) for w in caught]
