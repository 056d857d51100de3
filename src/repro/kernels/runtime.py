"""Where the kernels run.

Every kernel wrapper (`<kernel>/ops.py`) defaults to the compiled Pallas
path: ``backend="pallas"``, ``interpret=False``.  Nothing looks at the
device to pick another path.  The pure-jnp reference (``backend="xla"``)
and Pallas interpret mode (``interpret=True``) run only where a caller
asks for them, as the CPU tests and the dry-run do.  Off a TPU the
compiled path fails when Mosaic lowers it, loudly, instead of quietly
interpreting.
"""

from __future__ import annotations

import jax


def on_tpu() -> bool:
    """True when JAX's default device is a TPU.  A backend that fails to
    initialise raises; it is not read as "no TPU"."""
    return jax.devices()[0].platform == "tpu"
