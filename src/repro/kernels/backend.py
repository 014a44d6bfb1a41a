"""Pallas backend capability: which compiled target (if any) exists here.

``default_interpret`` is the ONE switch every kernel entry point resolves
against (``interpret=None`` in the public wrappers and the raw factories
alike): interpret mode runs the kernel body as traced JAX ops — the CPU
validation harness — while compiled mode lowers through Mosaic.  The
kernels use TPU memory spaces (VMEM residency, SMEM scalar staging), so
the TPU is the only compiled target:

- ``tpu``  -> Mosaic lowering exists  -> compiled (interpret=False)
- anything else                       -> interpret (interpret=True)

The resolved mode is logged exactly once per process so a silent fall-back
to interpret mode is visible in any log.
"""

from __future__ import annotations

import logging
from typing import Optional

import jax

logger = logging.getLogger("repro.kernels")

# jax.default_backend() -> the Pallas compiled lowering it can drive
_COMPILED_TARGETS = {"tpu": "mosaic"}

_logged_mode = False


def compiled_backend() -> Optional[str]:
    """Name of the compiled Pallas target for this process's default JAX
    backend ("mosaic"), or None when only interpret mode can execute."""
    return _COMPILED_TARGETS.get(jax.default_backend())


def default_interpret() -> bool:
    """Resolved interpret flag for every kernel whose caller passed None.

    False whenever a compiled Pallas target exists for the default backend
    (TPU/Mosaic), True otherwise.  Logs the resolution once.
    """
    global _logged_mode
    target = compiled_backend()
    interpret = target is None
    if not _logged_mode:
        _logged_mode = True
        if interpret:
            logger.info(
                "pallas kernels default to interpret mode (backend=%s has "
                "no compiled Pallas target)", jax.default_backend())
        else:
            logger.info(
                "pallas kernels default to compiled mode (backend=%s -> %s)",
                jax.default_backend(), target)
    return interpret
