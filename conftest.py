"""Every test process gets four virtual CPU devices, so that the cells and
code paths that span a (2, 2) mesh run in-process as they do on a four-chip
host (``jax.devices()`` then lists four; one-device code uses the first).
The flag must be in place before JAX starts its CPU backend."""
import os

_FLAG = "--xla_force_host_platform_device_count"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = \
        f"{os.environ.get('XLA_FLAGS', '')} {_FLAG}=4".strip()
