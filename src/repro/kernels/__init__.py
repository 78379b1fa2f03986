"""Pallas TPU kernels for the hot spots of DOPPLER's device path."""
from __future__ import annotations

import jax


def default_interpret() -> bool:
    """Where an ``interpret=None`` kernel argument resolves: compiled
    Mosaic on a TPU, the Pallas interpreter on every other backend.

    The DOPPLER-path entry points (``wc_step``, ``segment_sum_mp``, the
    WC oracle, fused Stage II) resolve it in Python before their
    ``jax.jit`` boundary, so a trace cached under ``interpret=None``
    cannot outlive a change of backend (the compile tests steer
    ``jax.default_backend``)."""
    return jax.default_backend() != "tpu"
