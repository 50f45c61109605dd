"""Round-20 tool wiring.

* ``tools/chip_queue.sh`` CHIP_QUEUE_DRY_RUN=1: the measurement queue
  runs end-to-end on CPU — heavy chip legs print-and-skip, while the
  kernel-variant sweep and the graftsched train-schedule winner legs
  execute tiny interpret-mode workloads and validate their artifact
  contracts.  A flag or JSON drift in the queue fails HERE, in tier-1,
  not mid-chip-window.
* ``bench.py --schedule-config``: the autotune winner loader fails
  fast (before the ResNet build) on a malformed config.
* ``tools/graftcost.py --kernel-plans``: the per-layer fused-BN
  kernel-plan table pins the selections at the real VMEM budget, every
  operand's window counted as the chip's compiler counts it — lane-fold
  stem forward, spatial-tiled 56x56 exits, tiled backward at 28x28,
  whole-L below — and accounts for all 53 BN layers of ResNet-50.
"""
import importlib.util
import json
import os
import subprocess

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_cli(name, path):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_queue_dry_run(tmp_path):
    env = dict(os.environ, CHIP_QUEUE_DRY_RUN="1", JAX_PLATFORMS="cpu",
               TMPDIR=str(tmp_path))
    log = tmp_path / "queue.log"
    r = subprocess.run(
        ["bash", os.path.join(ROOT, "tools", "chip_queue.sh"), str(log)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=280)
    out = log.read_text() if log.exists() else r.stdout
    assert r.returncode == 0, out[-2000:]
    # the artifact-producing legs actually ran and their contracts held
    assert "kernel-variant sweep contract ok" in out, out[-2000:]
    assert "schedule-winner contract ok" in out, out[-2000:]
    # chip legs were skipped, not silently attempted on CPU
    assert "[dry-run] skip" in out
    assert "== done" in out
    # its scratch files lived under $TMPDIR, in a directory of the run's
    # own, and are gone: nothing is shared with another run on the machine
    assert os.listdir(tmp_path) == ["queue.log"], os.listdir(tmp_path)
    script = open(os.path.join(ROOT, "tools", "chip_queue.sh")).read()
    assert "/tmp" not in script


def test_bench_schedule_config_rejects_malformed(tmp_path):
    bench = _load_cli("bench_cli", "bench.py")
    bad = tmp_path / "winner.json"
    bad.write_text(json.dumps({"target": "train-schedule", "knobs": {}}))
    # the loader runs BEFORE the ResNet build: a malformed winner config
    # costs an exception, not a model build + trace
    with pytest.raises(ValueError, match="schedule"):
        bench.run_train(schedule_config=str(bad))


def test_graftcost_kernel_plans_table(capsys):
    gc = _load_cli("graftcost_cli", "tools/graftcost.py")
    rc = gc.main(["--model", "resnet50", "--kernel-plans", "--batch",
                  "256", "--compute-dtype", "bfloat16", "--format",
                  "json"])
    assert rc == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["bn_group"] == 16 and payload["itemsize"] == 2
    layers = {r["layer"]: r for r in payload["layers"]}
    # all 53 BN layers accounted: stem + 16 blocks x 3 + 4 shortcuts
    assert sum(r["count"] for r in payload["layers"]) == 53
    stem = layers["stem"]
    assert stem["variant"] == "lanefold" and stem["fold"] == 2
    assert stem["window_mb"] == 25.7  # 51.4 MB whole-L halved
    # gY, X, dX at 2 x 25.7 MB each do not fit VMEM even folded
    assert stem["bwd"] == "jnp"
    ex = layers["stage1.exit"]
    assert ex["variant"] == "tiled" and ex["bwd"] == "tiled"
    assert ex["l_tile"] == 1568 and ex["l_tile_bwd"] == 784 and ex["dual"]
    # donating the residual saves its HBM buffer, not its VMEM window: the
    # downsample exit plans like the identity exits and shares their row
    assert ex["count"] == 3 and "donate" not in ex
    # 28x28x512 exits: whole-L fwd, the 6-window dual bwd tiles
    assert layers["stage2.exit"]["variant"] == "fused" \
        and layers["stage2.exit"]["bwd"] == "tiled"
    # everything from 14x14 down stays whole-L fused both ways
    for name in ("stage3.exit", "stage4.exit", "stage4.exit.tail"):
        assert (layers[name]["variant"], layers[name]["bwd"]) == \
            ("fused", "fused"), (name, layers[name])
    assert layers["stage4.exit.tail"]["dual"] is False

    rc = gc.main(["--model", "resnet50", "--kernel-plans",
                  "--compute-dtype", "bfloat16", "--batch", "256"])
    assert rc == 0
    table = capsys.readouterr().out
    assert "lanefold" in table and "tiled" in table
