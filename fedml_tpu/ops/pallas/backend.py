"""Where the Pallas kernels run: compiled by Mosaic on a TPU backend, through
the Pallas interpreter anywhere else (the CPU test suite).  The one place
that decision lives — every kernel entry point takes ``interpret=None`` and
resolves it here."""

from __future__ import annotations

import jax


def resolve_interpret(interpret=None) -> bool:
    """``interpret`` as given; ``None`` derives it from the active backend.

    The derived value is a convenience for CPU tests, not a guarantee: a
    process that lost its chip would run every kernel interpreted and still
    report success.  A path that measures or attests the compiled kernels
    (``chip_smoke.py``, the benchmark) asserts the backend is ``tpu`` first."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
