"""Opt-in JAX profiler hooks.

Two layers, both free when unused:

- :func:`profile_trace` wraps ``jax.profiler.trace`` for a whole run
  (``--profile-dir`` on serve.py / benchmarks.run); a ``None`` dir is a
  no-op context.
- :func:`annotate` names host-side stage boundaries with
  ``jax.profiler.TraceAnnotation`` so device timelines line up with
  the serving runtime's phases (``repro/tick``, ``repro/reset``,
  ``repro/search``). Inside jitted code we use ``jax.named_scope``
  instead (trace-time metadata, zero runtime cost) — see
  core/engine.py.

A trace that was asked for and cannot start raises: a run that was meant
to be measured must not pass for one that was. :func:`annotate` still
degrades to a null context, since it only names spans.
"""
from __future__ import annotations

import contextlib
from typing import Optional


@contextlib.contextmanager
def profile_trace(profile_dir: Optional[str]):
    """Capture a jax profiler trace into ``profile_dir`` (viewable with
    TensorBoard / Perfetto). ``None`` disables; a profiler that cannot
    start raises."""
    if not profile_dir:
        yield
        return
    import jax
    with jax.profiler.trace(profile_dir):
        yield


def annotate(name: str):
    """Named host-span for the device timeline; null context if the
    profiler annotation API is unavailable."""
    try:
        import jax
        return jax.profiler.TraceAnnotation(name)
    except Exception:  # noqa: BLE001
        return contextlib.nullcontext()
