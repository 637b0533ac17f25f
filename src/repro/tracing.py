"""Host spans of the solve, written into the JAX profiler's own trace.

Each span is a ``jax.profiler.TraceAnnotation``: when a profiler session is
running (``jax.profiler.trace`` / ``start_trace``) it lands on the host
thread that opened it, on the same clock as the device's operations, with
its keyword arguments as numeric or string stats; when none is running it
costs one inactive check (about a microsecond).  There is no switch: no
profiler session means no spans.

The names below are a contract: benchmark readers and tests key on them.
A span nests under its caller on the same thread, and every span of one
``eigsh`` call lies inside that call's ``repro.eigsh``, whose ``request``
stat is the call's process-wide sequence number.

=============================  ==================================  ==============================
span                           where                               stats
=============================  ==================================  ==============================
``repro.eigsh``                ``api.frontend.eigsh``              ``request``, ``k``, ``policy``
``repro.session.get``          ``api.session.get_session``         ``hit`` (0 or 1)
``repro.session.fingerprint``  each request-path digest            ``bytes`` hashed, ``chunks``
``repro.engine.restarted``     ``EigenSession._run_restarted``     ``m``, ``k``, ``max_restarts``,
                                                                   ``sell_gather`` (1: the
                                                                   ``"sell"`` SpMV gathers ``x``
                                                                   with its Mosaic kernel, 0:
                                                                   XLA or another layout)
``repro.lanczos.step``         one fill step of the restarted      ``i``, ``cycle``, ``host_reads``
                               engine
``repro.restarted.jacobi``     the host Jacobi of ``T_hat``        ``m``
``repro.restarted.ritz``       restart compression, final          none
                               projection
``repro.session.finish``       ``EigenSession._finish``            none
=============================  ==================================  ==============================
"""

from __future__ import annotations

from jax.profiler import TraceAnnotation

__all__ = ["SPANS", "span"]

SPANS = (
    "repro.eigsh",
    "repro.session.get",
    "repro.session.fingerprint",
    "repro.engine.restarted",
    "repro.lanczos.step",
    "repro.restarted.jacobi",
    "repro.restarted.ritz",
    "repro.session.finish",
)


def span(name: str, **meta) -> TraceAnnotation:
    """Context manager for the span ``name`` (one of ``SPANS``) with ``meta``
    as its stats."""
    return TraceAnnotation(name, **meta)
