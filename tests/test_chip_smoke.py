"""``chip_smoke.py`` off the chip: its phases are plain functions of their
sizes, so the same code runs here at toy sizes on the CPU (four of
conftest's eight virtual devices for the mesh);
and the script itself, run as the driver runs it, must FAIL here — this
machine has no accelerator."""
import inspect
import json
import os
import subprocess
import sys
import types

import pytest

import bench
import chip_smoke
from incubator_mxnet_tpu import _backend

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# a toy-sized ResNet-50 (BatchNorm over 8 values at its 1x1 stages) does
# not train at the recipe's lr=0.1; everything else is the recipe's
_TOY = dict(image_size=32, classes=10, learning_rate=0.005)


def test_train_phase_tiny_on_cpu():
    out = chip_smoke.train(batch=8, steps=6, platform="cpu", **_TOY)
    assert len(out["losses"]) == 7 and out["compile_s"] > 0


def test_placement_check_notices_state_off_the_device():
    """The check a silent ``ctx=mx.tpu()``-on-a-cpu-host run must not get
    through: asked for ``tpu``, arrays that live on the cpu fail it."""
    import jax.numpy as jnp

    here = [jnp.ones(3), jnp.zeros((2, 2))]
    chip_smoke._check_placed(here, "cpu", "state")
    with pytest.raises(chip_smoke.SmokeFailure, match="not on a tpu device"):
        chip_smoke._check_placed(here, "tpu", "state")


def test_serve_phase_tiny_on_cpu():
    rep = chip_smoke.serve(buckets=(2, 4), image_size=32, n_requests=12,
                           qps=50.0, n_check=4, classes=10)
    assert rep.ok == 12 and rep.recompiles == 0


@pytest.mark.parametrize("act", ["relu", "silu"])
def test_experts_phase_tiny_on_cpu(act, monkeypatch):
    """Here the interpreter fills what the op's kernels leave unwritten with
    NaN besides; and the phase does notice a NaN that is let through."""
    assert chip_smoke.experts(1536, 16, 16, 4, act, platform="cpu") == 229
    from incubator_mxnet_tpu.parallel import moe

    sound = moe.moe_experts
    monkeypatch.setattr(moe, "moe_experts", lambda rows, *a, **kw: sound(
        rows, *a, **kw) + rows[-1, 0])
    with pytest.raises(chip_smoke.SmokeFailure, match="reached ys"):
        chip_smoke.experts(1536, 16, 16, 4, act, platform="cpu")


def test_multichip_phase_tiny_on_four_virtual_devices():
    # loss_rtol: bf16 rounding noise at this size reaches 7 % (in float32
    # the two first losses agree to 2e-4, see MULTICHIP_LOSS_RTOL)
    losses = chip_smoke.multichip(dp=4, batch=8, steps=6, platform="cpu",
                                  loss_rtol=0.1, **_TOY)
    assert len(losses) == 6


def test_a_mesh_wider_than_the_devices_is_an_error_not_a_smaller_mesh():
    with pytest.raises(RuntimeError, match="needs 16 devices"):
        bench.dp_mesh(16)


def test_smoke_and_bench_share_one_definition(monkeypatch):
    """``train`` with no composition arguments builds what ``bench.py``
    builds with no flags: both read bench.DEFAULT_PASSES and bench.DEFAULT_ZERO
    and go through bench.build_train_step."""
    seen = {}
    for fn in (bench.run_train, bench.build_train_step):
        params = inspect.signature(fn).parameters
        assert params["passes"].default == bench.DEFAULT_PASSES
        assert params["zero"].default == bench.DEFAULT_ZERO

    def spy(**kwargs):
        seen.update(kwargs)
        raise KeyboardInterrupt  # got what we came for

    monkeypatch.setattr(bench, "build_train_step", spy)
    with pytest.raises(KeyboardInterrupt):
        chip_smoke.train(batch=8, steps=1, platform="cpu", **_TOY)
    assert seen["passes"] == bench.DEFAULT_PASSES
    assert "zero" not in seen and "mesh" not in seen


def _run_here(script, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, os.path.join(_REPO, script),
                           *args], cwd=_REPO, env=env, capture_output=True,
                          text=True, timeout=280)


@pytest.mark.parametrize("args", [(), ("--multichip",)],
                         ids=["one-chip", "multichip"])
def test_chip_smoke_fails_without_an_accelerator(args):
    run = _run_here("chip_smoke.py", *args)
    assert run.returncode != 0, run.stdout[-2000:]
    assert '"ok": true' not in run.stdout
    assert "needs 'tpu'" in run.stderr, run.stderr[-2000:]


def test_bench_fails_without_an_accelerator():
    run = _run_here("bench.py", "--chunks", "1")
    assert run.returncode != 0, run.stdout[-2000:]
    assert not any(line.startswith("{") and "metric" in json.loads(line)
                   for line in run.stdout.splitlines()
                   if line.startswith("{")), run.stdout[-2000:]
    assert "measures the TPU" in run.stderr, run.stderr[-2000:]


def test_peak_rate_of_an_unlisted_device_kind_is_an_error():
    assert bench.peak_bf16_flops("TPU v5 lite") == 197e12
    with pytest.raises(RuntimeError, match="no published bf16 peak"):
        bench.peak_bf16_flops("cpu")


def _fake_jax(monkeypatch):
    calls = []
    fake = types.SimpleNamespace(config=types.SimpleNamespace(
        update=lambda name, value: calls.append((name, value))))
    monkeypatch.setattr(_backend, "jax", fake)
    return calls


def test_compile_cache_follows_the_environment_when_it_is_set(monkeypatch,
                                                              tmp_path):
    calls = _fake_jax(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    assert _backend.use_compile_cache() == str(tmp_path / "cc")
    assert "jax_compilation_cache_dir" not in [name for name, _ in calls]
    assert not (tmp_path / "cc").exists()  # jax's to make, not ours


def test_compile_cache_is_in_the_checkout_otherwise(monkeypatch):
    calls = _fake_jax(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(_REPO, ".jax_cache")
    assert _backend.use_compile_cache() == want
    assert ("jax_compilation_cache_dir", want) in calls


@pytest.mark.parametrize("backend,want", [("cpu", True), ("tpu", False)])
def test_pallas_interpret_exactly_on_cpu(monkeypatch, backend, want):
    monkeypatch.setattr(_backend, "jax", types.SimpleNamespace(
        default_backend=lambda: backend))
    assert _backend.pallas_interpret() is want


def test_pallas_interpret_refuses_any_other_backend(monkeypatch):
    monkeypatch.setattr(_backend, "jax", types.SimpleNamespace(
        default_backend=lambda: "gpu"))
    with pytest.raises(RuntimeError, match="'gpu'"):
        _backend.pallas_interpret()


def test_importing_the_package_initializes_no_backend():
    """One process per chip: the chip goes to whoever first asks jax for
    its devices, and importing the library must not be that."""
    code = ("import incubator_mxnet_tpu; from jax._src import xla_bridge; "
            "assert not xla_bridge._backends, dict(xla_bridge._backends)")
    run = subprocess.run([sys.executable, "-c", code], cwd=_REPO,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=120)
    assert run.returncode == 0, run.stderr[-2000:]


def test_kda_phase_tiny_on_cpu():
    """The phase's kernels (interpreted) against the recurrence token by
    token at two chunks and a part of a third, and against the chunked form
    at two chunks, two heads of 32."""
    assert chip_smoke.kda(128, 2, 32, platform="cpu", witness=(150, 2)) == 128


def test_kda_phase_witness_catches_what_both_chunked_forms_share(
        monkeypatch):
    """A fault in the chunk mathematics that the kernels and the chunked
    form share (here the backward's beta gradient 10 % high) passes the
    comparison of the two and is caught by the token-by-token witness."""
    import jax

    from incubator_mxnet_tpu.parallel import delta_rule

    was = delta_rule._chunk_bwd

    def off(*args):
        *grads, dbeta, dz = was(*args)
        return (*grads, dbeta * 1.1, dz)

    def fresh():
        # the traced kernels and chunked form are cached
        jax.clear_caches()

    monkeypatch.setattr(delta_rule, "_chunk_bwd", off)
    fresh()
    try:
        with pytest.raises(chip_smoke.SmokeFailure,
                           match="dbeta of the kernels .* from the "
                           "recurrence"):
            chip_smoke.kda(128, 2, 32, platform="cpu", witness=(150, 2))
    finally:
        monkeypatch.undo()
        fresh()
