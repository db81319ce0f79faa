"""Host spans of the serving engine, as profiler trace events.

Every span is a ``jax.profiler.TraceAnnotation``: while a profiler trace
runs (``jax.profiler.start_trace`` / ``trace``) it lands in the same
trace as the device's operations, on the same clock; otherwise entering
one costs a flag check. docs/SERVING.md "Profiling" says how to read
them.

``SPANS`` is the whole set: each name with the integer args it carries.
A ``<kind>.launch`` span covers packing the host arrays, uploading them
and calling the jitted step; ``<kind>.wait`` covers bringing the logits
to the host (the device finishing the step included); ``<kind>.emit``
the host's token picks and bookkeeping after it.
"""
from __future__ import annotations

import jax

SPANS: dict[str, tuple[str, ...]] = {
    "serve.submit": (),
    # state at the start of the tick: requests queued, slots prefilling,
    # slots decoding
    "serve.tick": ("queued", "prefilling", "decoding"),
    # requests placed in slots this tick, residents preempted for them
    "serve.admit": ("admitted", "preempted"),
    "serve.prefill": (),
    # rows the program computes (batch_slots x c_pad, or the prompt of a
    # dense prefill), prompt tokens among them, the chunk's padded width
    "serve.prefill.launch": ("rows", "tokens", "c_pad"),
    "serve.prefill.wait": ("bytes",),
    "serve.prefill.emit": (),
    "serve.decode": (),
    # rows computed (batch_slots x window), valid rows (drafts included)
    "serve.decode.launch": ("rows", "tokens"),
    "serve.decode.wait": ("bytes",),
    "serve.decode.emit": (),
}


def span(name: str, **args: int) -> jax.profiler.TraceAnnotation:
    """The trace event ``name`` (a key of ``SPANS``) with ``args``; use as
    a context manager. Args known only at the end are added with
    ``set_metadata`` on the entered span."""
    return jax.profiler.TraceAnnotation(name, **args)
