"""Pin jax to N virtual XLA-CPU devices — the single copy of the
"run off the chip" recipe used by tests/conftest.py and
__graft_entry__.dryrun_multichip.

Import-light: importing this module does not import jax; ``pin_cpu`` sets
env vars first and only then imports jax, so it works as long as no jax
backend has been initialized yet in the process.
"""
import os
import re


def pin_cpu(n_devices: int = 8):
    """Force cpu-only jax with >= n_devices virtual host devices."""
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    if m is None:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=%d" % n_devices
        ).strip()
    elif int(m.group(1)) < n_devices:
        os.environ["XLA_FLAGS"] = flags.replace(
            m.group(0), "--xla_force_host_platform_device_count=%d" % n_devices)
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    # jax read $JAX_PLATFORMS when it was first imported, which may have
    # been before this call: say it to the config too (takes effect as
    # long as no backend is up)
    jax.config.update("jax_platforms", "cpu")
    return jax
