"""Tracing hooks: name the hot path for the jax profiler.

Two kinds of annotation, matching where the code runs:

  * :func:`span` — ``jax.named_scope`` for *traced* code.  Zero runtime
    cost (it only labels operations during tracing), but every bucketed
    factor / precondition launch then shows up in a captured profile —
    and in dumped HLO — under a readable ``kfac/...`` path instead of a
    fusion soup.
  * :func:`host_span` — ``jax.profiler.TraceAnnotation`` for *host*
    code (the training loops' per-step phases below, the
    AsyncInverseRunner's worker thread), which emits a real TraceMe at
    runtime on the profile's host track, on the same clock as the
    device's ops.

Device scopes on the training step: ``model`` (the loss, so forward ops
read ``jvp(model)/...`` and backward ops ``transpose(jvp(model))/...``),
``update`` (the optimizer's update and its application; the K-FAC work
inside it reads ``update/kfac/factor/...`` and ``update/kfac/precond/...``)
and, inside a Brand light update, ``brand_panel``, ``brand_qr``,
``brand_core`` and ``brand_rotate`` (arXiv:2210.08494, Alg. 3).

Host spans of one training-loop iteration, in order: :data:`SCHEDULE`
(the step's work mask, remediation and async landings), :data:`DISPATCH`
(the step call), :data:`LOSS_SYNC` (the wait for the step's loss) and
:data:`CALLBACK` (the caller's per-step hook).

:class:`StepProfiler` drives ``--profile-dir``: capture a profiler
trace for a contiguous window of training steps (skipping step 0 by
default so compilation doesn't drown the steady state).
"""
from __future__ import annotations

import contextlib
from typing import Optional

import jax

#: host spans of one training-loop iteration (``train/loop.py``,
#: ``launch/train.py``), in the order they run
SCHEDULE = "train/schedule"
DISPATCH = "train/dispatch"
LOSS_SYNC = "train/loss_sync"
CALLBACK = "train/callback"


@contextlib.contextmanager
def span(name: str):
    """Label traced operations (named_scope) — nestable, trace-time only."""
    with jax.named_scope(name):
        yield


@contextlib.contextmanager
def host_span(name: str):
    """Label host-side work with a runtime profiler TraceAnnotation."""
    with jax.profiler.TraceAnnotation(name):
        yield


class StepProfiler:
    """Capture a jax profiler trace for steps [first, first+steps).

    ``tick(k)`` brackets the capture from the training loop;
    ``close()`` stops a still-running capture (early exit).  Inactive
    (``log_dir=None``) instances are no-ops, so the loop can call
    ``tick`` unconditionally."""

    def __init__(self, log_dir: Optional[str], first: int = 1,
                 steps: int = 3):
        self.log_dir = log_dir or None
        self.first = int(first)
        self.last = int(first) + int(steps)     # exclusive
        self._running = False

    def tick(self, k: int) -> None:
        if self.log_dir is None:
            return
        if not self._running and self.first <= k < self.last:
            jax.profiler.start_trace(self.log_dir)
            self._running = True
        elif self._running and k >= self.last:
            jax.profiler.stop_trace()
            self._running = False

    def close(self) -> None:
        if self._running:
            jax.profiler.stop_trace()
            self._running = False
