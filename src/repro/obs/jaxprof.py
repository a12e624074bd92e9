"""Adapter between :mod:`repro.obs` and the JAX profiler.

Two directions of the same timeline:

* **obs spans → JAX profiler**: a :class:`~repro.obs.trace.Tracer`
  enabled with ``jax_annotations=True`` mirrors every span into a
  ``jax.profiler.TraceAnnotation``, so the driver-level structure
  (``tick.place``, ``sweep.chunk``, ...) shows up inside the JAX/XLA
  profile next to the kernels it wraps.
* **host work → obs**: :func:`kernel_span` is an obs span named
  ``kernel.<x>`` for host work that stands in for a kernel (the serving
  horizon's NumPy QoS matrix), on the same timeline as the tick spans.
  It times the host: around an asynchronous JAX dispatch it would time
  the enqueue, not the kernel. Device time comes from
  :func:`named_scope`, whose ``jax.named_scope`` tags the emitted HLO so
  kernel time is attributable in ``jax.profiler`` dumps.

:func:`profile_trace` wraps ``jax.profiler.trace`` (TensorBoard /
Perfetto-loadable ``plugins/profile`` dumps); everything degrades to a
no-op when JAX or its profiler is unavailable, so obs never adds a hard
dependency.
"""
from __future__ import annotations

import contextlib
from typing import Any, Iterator, Optional

from . import trace as _trace

__all__ = ["kernel_span", "named_scope", "profile_trace",
           "have_jax_profiler"]


def have_jax_profiler() -> bool:
    try:
        import jax.profiler  # noqa: F401
        return True
    except Exception:  # pragma: no cover - jax-less install
        return False


def kernel_span(name: str, **args: Any):
    """An obs span in the ``kernel`` category (``kernel.<name>``) —
    recorded on the obs timeline and, when the tracer runs with JAX
    annotations, on the JAX profiler timeline as well. No-op (the shared
    null span) when tracing is disabled."""
    full = name if name.startswith("kernel.") else "kernel." + name
    return _trace.span(full, **args)


def named_scope(name: str):
    """``jax.named_scope`` when JAX is importable, else a null context —
    tags HLO emitted under it so kernel time is attributable in profiler
    dumps. Safe inside jitted code (it is a trace-time annotation)."""
    try:
        import jax
        return jax.named_scope(name)
    except Exception:  # pragma: no cover - jax-less install
        return contextlib.nullcontext()


@contextlib.contextmanager
def profile_trace(log_dir, *, create_perfetto_link: bool = False
                  ) -> Iterator[Optional[str]]:
    """Run the body under ``jax.profiler.trace(log_dir)``.

    Yields the log dir on success or ``None`` when the profiler is
    unavailable (the body still runs). Combine with an obs tracer enabled
    with ``jax_annotations=True`` to see driver spans inside the dump::

        obs.enable(jax_annotations=True)
        with profile_trace("/tmp/jaxprof"):
            run_sweep(spec)
    """
    try:
        import jax.profiler as prof
    except Exception:  # pragma: no cover - jax-less install
        yield None
        return
    prof.start_trace(str(log_dir),
                     create_perfetto_link=create_perfetto_link)
    try:
        yield str(log_dir)
    finally:
        prof.stop_trace()
