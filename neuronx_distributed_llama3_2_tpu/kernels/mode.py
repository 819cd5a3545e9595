"""Kernel mode: the one place that maps a platform to how kernels run.

Every Pallas call site and every dispatcher that can choose between a
Pallas kernel and its jnp twin reads the mode from here, so no kernel file
consults ``jax.default_backend()`` on its own and a process that lost its
TPU cannot drift onto a host path and still exit 0.

================  ===========================  ==========================
mode              ``pallas_call`` sites        Pallas-or-jnp dispatchers
================  ===========================  ==========================
``"compiled"``    Mosaic (``interpret=False``)  the Pallas kernel
``"interpret"``   the Pallas interpreter        the Pallas kernel
``"reference"``   the Pallas interpreter        the jnp reference
================  ===========================  ==========================

A TPU gets ``"compiled"`` by default. Every other mode is something the
caller asks for by name in ``NXDT_KERNEL_MODE``: the CPU test tier names
``"reference"`` (``tests/conftest.py`` — interpreting a training-shape
flash kernel costs minutes a step, the jnp twin is the same math),
``chip_smoke.py --rehearse-on-cpu`` names ``"interpret"``, and the
cross-lowering test names ``"compiled"`` on a CPU host. A non-TPU platform
with nothing asked for is an error, not a fallback.
"""

from __future__ import annotations

import os

import jax

KERNEL_MODE_ENV = "NXDT_KERNEL_MODE"
KERNEL_MODES = ("compiled", "interpret", "reference")


def kernel_mode() -> str:
    """The mode in force: ``NXDT_KERNEL_MODE`` when set, else ``"compiled"``
    on a TPU. Read at trace time, so it must be set before the first jit."""
    asked = os.environ.get(KERNEL_MODE_ENV)
    if asked is not None:
        if asked not in KERNEL_MODES:
            raise ValueError(
                f"{KERNEL_MODE_ENV}={asked!r}: expected one of {KERNEL_MODES}"
            )
        return asked
    platform = jax.default_backend()
    if platform == "tpu":
        return "compiled"
    raise RuntimeError(
        f"no kernel mode for platform {platform!r}: Pallas kernels compile "
        f"for a TPU only. If the TPU was expected, its runtime failed to "
        f"start; to run on this platform on purpose set {KERNEL_MODE_ENV} to "
        f"'interpret' (Pallas interpreter) or 'reference' (jnp twins)."
    )


def pallas_interpret() -> bool:
    """``interpret=`` for a ``pallas_call`` site."""
    return kernel_mode() != "compiled"


def prefer_pallas() -> bool:
    """Whether a dispatcher with a jnp twin takes the Pallas kernel."""
    return kernel_mode() != "reference"
