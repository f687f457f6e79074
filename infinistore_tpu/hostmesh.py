"""Keeping JAX on the CPU platform where a process must not touch the chip.

``force_cpu_devices`` gives THIS process an n-device virtual CPU backend: it
is for the test suite and ``__graft_entry__.dryrun_multichip`` only — no
entry point a user runs on the chip may call it. It must run before JAX
initializes any backend: XLA flags are consumed once, at first backend
creation. ``cpu_child_env`` is the launch-site half: the environment for a
JAX-importing child of a process that holds the accelerator.
"""

import os


def cpu_child_env() -> dict:
    """Environment for a child process that imports JAX while THIS process
    holds the machine's accelerator: the parent's environment with the
    platform pinned to cpu. A chip belongs to one process at a time, so a
    child that inherited the default platform would fail or hang reaching
    for it. Pass as ``env=`` at the launch site."""
    return {**os.environ, "JAX_PLATFORMS": "cpu"}


def force_cpu_devices(n_devices: int = 8) -> None:
    """Pin JAX to a CPU backend with ``n_devices`` virtual devices.

    Pins the platform list to cpu and sets
    ``--xla_force_host_platform_device_count``. A caller-provided count >=
    ``n_devices`` is honored (e.g. running tests on a bigger virtual mesh); a
    smaller one can't satisfy the requirement and is replaced with a warning.
    No-op for the flag if backends are already initialized (too late to
    change — invoke before the first jax operation).
    """
    import re

    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    flag = f"--xla_force_host_platform_device_count={n_devices}"
    m = re.search(r"--?xla_force_host_platform_device_count=(\d+)", flags)
    if m is None:
        flags = (flags + " " + flag).strip()
    elif int(m.group(1)) < n_devices:
        import warnings

        warnings.warn(
            f"XLA_FLAGS forces {m.group(1)} host devices but {n_devices} are "
            f"required; overriding to {n_devices}"
        )
        flags = flags[: m.start()] + flag + flags[m.end():]
    os.environ["XLA_FLAGS"] = flags
    import jax

    # jax reads JAX_PLATFORMS when it is first imported; a caller that
    # imported jax earlier (without touching a backend) needs the config set.
    jax.config.update("jax_platforms", "cpu")
