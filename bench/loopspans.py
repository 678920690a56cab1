"""Reductions over the program's own names: the device scopes of the
training step (``model``, ``update``, the Brand update's phases) and the
host spans of the training loop (``train/dispatch``), for the per-layer
metrics that read them.

A scope is matched as a whole part of an op's name-stack path, so a
program that lacks it reads as absent, never as another scope: forward
ops read ``jit(step)/jvp(model)/...``, backward ops
``jit(step)/transpose(jvp(model))/...``, the optimizer's update
``jit(step)/update/...`` with its K-FAC work under ``update/kfac/``.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import devtrace

#: the training loop's host span around the step call
#: (``repro/obs/trace.py`` ``DISPATCH``)
DISPATCH_SPAN = "train/dispatch"
#: ... around the wait for the step's loss (``LOSS_SYNC``)
LOSS_SYNC_SPAN = "train/loss_sync"

#: the phases of one Brand light update (arXiv:2210.08494, Alg. 3)
BRAND_CORE = ("brand_core",)
BRAND_LINEAR = ("brand_panel", "brand_qr", "brand_rotate")


def _parts(scope: str) -> List[str]:
    """The path parts of an op's scope, the op's own name left out."""
    return scope.split("/")[:-1]


def is_forward(scope: str) -> bool:
    parts = _parts(scope)
    return ("jvp(model)" in parts and not devtrace.is_kfac(scope)
            and not any(p.startswith("transpose(") for p in parts))


def is_backward(scope: str) -> bool:
    return ("transpose(jvp(model))" in _parts(scope)
            and not devtrace.is_kfac(scope))


def is_update(scope: str) -> bool:
    return "update" in _parts(scope) and not devtrace.is_kfac(scope)


def brand_phase(scope: str) -> Optional[str]:
    """The first ``brand_*`` part of a light-Brand op's path, else None."""
    if not devtrace.is_light_brand(scope):
        return None
    for p in _parts(scope):
        if p.startswith("brand_"):
            return p
    return None


def per_step_ms(parsed: dict, steps: int, keep) -> Optional[float]:
    """Device ms per step of the ops ``keep`` accepts; None where none."""
    t = devtrace.scope_ns(parsed, keep)
    if not steps or t <= 0:
        return None
    return t / 1e6 / steps


def per_light_step_ms(parsed: dict, phases) -> Optional[float]:
    """Device ms, per step that runs a Brand light update, of its ops
    whose first ``brand_*`` part is one of ``phases``; None where none."""
    light = devtrace.per_step_scope_ns(parsed, devtrace.is_light_brand)
    mine = devtrace.per_step_scope_ns(
        parsed, lambda s: brand_phase(s) in phases)
    picked = [m for lt, m in zip(light, mine) if lt > 0]
    if not picked or sum(picked) <= 0:
        return None
    return sum(picked) / len(picked) / 1e6


def idle_gaps(parsed: dict) -> List[Tuple[float, float]]:
    """The intervals of the window in which no op ran on any chip: the
    window less the union of the op intervals."""
    lo, hi = devtrace.window(parsed)
    iv = sorted((max(s, lo), min(s + d, hi)) for s, d, *_ in parsed["ops"]
                if s + d > lo and s < hi)
    gaps, cur = [], lo
    for s, e in iv:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    return gaps


def spans(parsed: dict, name: str) -> List[Tuple[float, float]]:
    """(start, end) of each host event called ``name``."""
    return [(s, s + d) for s, d, n in parsed["host"] if n == name]


def idle_in_ns(parsed: dict, name: str) -> Optional[float]:
    """Device-idle ns of the window that lie inside a ``name`` span;
    None where the trace holds no such span."""
    inside = spans(parsed, name)
    if not inside:
        return None
    total = 0.0
    for gs, ge in idle_gaps(parsed):
        total += devtrace.union_ns(
            [(max(gs, s), min(ge, e)) for s, e in inside
             if s < ge and e > gs])
    return total


def dispatch_idle_ms(parsed: dict, steps: int) -> Optional[float]:
    t = idle_in_ns(parsed, DISPATCH_SPAN)
    if t is None or not steps:
        return None
    return t / 1e6 / steps


def loop_idle_ms(parsed: dict, steps: int) -> Optional[float]:
    inside = idle_in_ns(parsed, DISPATCH_SPAN)
    if inside is None or not steps:
        return None
    idle = sum(e - s for s, e in idle_gaps(parsed))
    return (idle - inside) / 1e6 / steps


def clock_offsets(parsed: dict) -> List[dict]:
    """For each step in the window, on the one timeline: the module's
    start less the end of the last ``train/dispatch`` span that began
    before it (negative: the step started while the host still
    dispatched), and the end of the first ``train/loss_sync`` span that
    ends after the module began, less the module's end (positive: the
    loss was read after the step ended).  In ms."""
    dispatch = spans(parsed, DISPATCH_SPAN)
    sync = spans(parsed, LOSS_SYNC_SPAN)
    out = []
    for s, d, *_ in devtrace.steps_in_window(parsed):
        before = [e for b, e in dispatch if b <= s]
        after = [e for b, e in sync if e > s]
        out.append({
            "start_after_dispatch_ms":
                (s - max(before)) / 1e6 if before else None,
            "sync_after_end_ms":
                (min(after) - (s + d)) / 1e6 if after else None})
    return out
