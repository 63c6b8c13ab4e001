"""Module-cached backend probe shared by every ``kernels/*/ops.py``.

Every op wrapper used to call ``jax.default_backend() != "tpu"`` on each
invocation to decide whether the Pallas kernel should run compiled or in
interpret mode.  Inside the scan engine that probe sat on the per-interval
hot path (one backend-registry lookup per op per interval per lane), so it
is resolved ONCE at import of the first op module and cached here.

On a TPU every kernel runs compiled.  Elsewhere kernels run in interpret
mode, and ``REPRO_FORCE_INTERPRET=1`` (any non-empty value other than
``0``) additionally routes the ops that default to their jnp references
off-TPU through the interpret-mode kernels — the switch the kernel-vs-ref
checks use on CPU hosts.  Setting it on a TPU backend is an error: it
would silently swap the chip's compiled kernels for the interpreter.
"""
from __future__ import annotations

import os

_INTERPRET: bool | None = None


def force_interpret() -> bool:
    """Did the environment pin interpret mode (``REPRO_FORCE_INTERPRET``)?"""
    return os.environ.get("REPRO_FORCE_INTERPRET", "0") not in ("", "0")


def interpret_mode() -> bool:
    """True when Pallas kernels run interpreted (any non-TPU backend).

    The backend probe runs once per process; jax backends cannot change
    after initialization, so caching is safe.  Raises on a TPU backend
    when ``REPRO_FORCE_INTERPRET`` is set.
    """
    global _INTERPRET
    if _INTERPRET is None:
        import jax

        on_tpu = jax.default_backend() == "tpu"
        if on_tpu and force_interpret():
            raise RuntimeError(
                "REPRO_FORCE_INTERPRET is set on a TPU backend; kernels "
                "always run compiled on a TPU — unset it")
        _INTERPRET = not on_tpu
    return _INTERPRET
