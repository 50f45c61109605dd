#!/usr/bin/env python
"""Environment diagnostic (reference: tools/diagnose.py — python/pip/
library/hardware/network checks for bug reports).  TPU-native version:
python + package + jax/backend + device + feature + config report.

Without ``--probe-backend`` the tool holds itself to the CPU: a chip
belongs to one process at a time, and a report must not be the one that
takes it.

Usage: python tools/diagnose.py [--probe-backend]
"""
import argparse
import os
import platform
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def check_python():
    print("----------Python Info----------")
    print("Version      :", platform.python_version())
    print("Compiler     :", platform.python_compiler())
    print("Build        :", platform.python_build())
    print("Arch         :", platform.architecture())


def check_hardware():
    print("----------Hardware Info----------")
    print("Machine      :", platform.machine())
    print("Platform     :", platform.platform())
    print("Processor    :", platform.processor() or "?")
    print("CPU cores    :", os.cpu_count())
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith(("MemTotal", "MemAvailable")):
                    print(line.strip())
    except OSError:
        pass


def check_package():
    print("----------Framework Info----------")
    import incubator_mxnet_tpu as mx
    print("Version      :", getattr(mx, "__version__", "?"))
    print("Location     :", os.path.dirname(mx.__file__))
    from incubator_mxnet_tpu.runtime import feature_list
    feats = [f.name for f in feature_list() if f.enabled]
    print("Features     :", ", ".join(feats) if feats else "-")
    from incubator_mxnet_tpu import config
    print("Config vars  : %d declared MXNET_* variables" % len(config.VARS))
    for name in sorted(config.VARS):
        if os.environ.get(name) is not None:
            print("Env          : %s=%s" % (name, os.environ[name]))


def check_jax(probe_backend, user_platforms):
    print("----------JAX Info----------")
    import jax
    print("jax          :", jax.__version__)
    import jaxlib
    print("jaxlib       :", jaxlib.__version__)
    # the user's ORIGINAL env, not the cpu pin main() sets
    print("JAX_PLATFORMS:", "<unset>" if user_platforms is None
          else user_platforms)
    if probe_backend:
        t0 = time.time()
        devs = jax.devices()
        print("Devices      : %s (init %.1fs)" % (devs, time.time() - t0))
    else:
        print("Devices      : (not asked; --probe-backend takes the "
              "accelerator for this process)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--probe-backend", action="store_true",
                    help="initialize the default jax backend and list its "
                         "devices (this process then holds the chip)")
    args = ap.parse_args()
    user_platforms = os.environ.get("JAX_PLATFORMS")
    if not args.probe_backend:
        os.environ["JAX_PLATFORMS"] = "cpu"  # before jax is imported
    check_python()
    check_hardware()
    check_package()
    check_jax(args.probe_backend, user_platforms)


if __name__ == "__main__":
    main()
