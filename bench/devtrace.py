"""Reads a JAX profiler trace (``*.xplane.pb``) into the plain form the
per-layer metrics reduce, and reduces what every metric shares.

The parsed form is a dict of lists, so a small recorded trace can be
kept as JSON beside the tests:

* ``ops``: ``[start_ns, dur_ns, scope, program_id, flops, bytes,
  self_ns]`` for every operation on the "XLA Ops" line of each TPU,
  where ``scope`` is the op's ``tf_op`` metadata: its JAX name-stack
  path, e.g. ``jit(step)/kfac/factor/b3_brand/light_brand/dot_general:``
  (Pallas kernels carry their kernel name there).  A control-flow op
  (``conditional``, ``while``) spans the ops of its body; ``self_ns`` is
  its duration less theirs, and what the time reductions add up.
* ``modules``: ``[start_ns, dur_ns, name, program_id]``, one per program
  execution (one training step) on the "XLA Modules" line.
* ``host``: ``[start_ns, dur_ns, name]`` for host events (the runtime's
  own and the benchmark's ``bench/...`` annotations).
* ``chips``: the number of TPU planes.

Times are in nanoseconds on the profiler's one timeline.
"""
from __future__ import annotations

import glob
import importlib.util
import pathlib
from typing import Dict, List, Tuple

#: the benchmark's host annotations, placed around the batch hand-over
#: and the step callback; the traced window runs from the first batch
#: hand-over to the end of the last callback
BATCH_SPAN = "bench/batch"
CALLBACK_SPAN = "bench/callback"


def _xplane_pb2():
    """The XSpace protobuf classes, loaded from the installed TensorFlow
    file by path: importing the ``tensorflow`` package for them would
    take a quarter of a minute."""
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or spec.origin is None:
        raise ImportError("the XSpace protobuf classes come with tensorflow, "
                          "which is not installed")
    path = (pathlib.Path(spec.origin).parent
            / "tsl" / "profiler" / "protobuf" / "xplane_pb2.py")
    mspec = importlib.util.spec_from_file_location("bench_xplane_pb2", path)
    mod = importlib.util.module_from_spec(mspec)
    mspec.loader.exec_module(mod)
    return mod


def _stat_value(stat):
    for field in ("str_value", "int64_value", "uint64_value",
                  "double_value"):
        if stat.HasField(field):
            return getattr(stat, field)
    return None


def parse_xspace(data: bytes) -> dict:
    """The parsed form of one serialized XSpace."""
    xs = _xplane_pb2().XSpace()
    xs.ParseFromString(data)
    ops: List[list] = []
    modules: List[list] = []
    host: List[list] = []
    chips = 0
    for plane in xs.planes:
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        if plane.name.startswith("/device:TPU:"):
            chips += 1
            meta = {}
            for mid, em in plane.event_metadata.items():
                st = {names.get(s.metadata_id): _stat_value(s)
                      for s in em.stats}
                meta[mid] = (str(st.get("tf_op") or em.display_name
                                 or em.name),
                             str(st.get("program_id") or ""),
                             float(st.get("flops") or 0),
                             float(st.get("bytes_accessed") or 0),
                             em.name)
            for line in plane.lines:
                if line.name not in ("XLA Ops", "XLA Modules"):
                    continue
                for ev in line.events:
                    start = line.timestamp_ns + ev.offset_ps / 1e3
                    dur = ev.duration_ps / 1e3
                    scope, prog, flops, nbytes, name = meta[ev.metadata_id]
                    if line.name == "XLA Ops":
                        ops.append([start, dur, scope, prog, flops, nbytes])
                    else:
                        modules.append([start, dur, name, prog])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    em = plane.event_metadata[ev.metadata_id]
                    host.append([line.timestamp_ns + ev.offset_ps / 1e3,
                                 ev.duration_ps / 1e3,
                                 em.display_name or em.name])
    return finish({"ops": ops, "modules": modules, "host": host,
                   "chips": chips})


def finish(parsed: dict) -> dict:
    """Sort the events and give each op its self time (a parsed trace
    kept as JSON stores the ops without it)."""
    for key in ("ops", "modules", "host"):
        parsed[key].sort(key=lambda e: e[0])
    parsed["ops"] = [list(op[:6]) for op in parsed["ops"]]
    _self_times(parsed["ops"])
    return parsed


def _self_times(ops: List[list]) -> None:
    """Append each op's self time: its duration less that of the ops
    that start and end inside it (the body of a control-flow op).  A
    control-flow op carries no name-stack path of its own; it takes its
    first inner op's, so its own time counts where its body does."""
    stack: List[list] = []
    for op in ops:
        op.append(op[1])
        end = op[0] + op[1]
        while stack and stack[-1][0] + stack[-1][1] <= op[0]:
            stack.pop()
        if stack and end <= stack[-1][0] + stack[-1][1]:
            parent = stack[-1]
            parent[6] -= op[1]
            if "/" not in parent[2]:
                parent[2] = op[2]
        stack.append(op)


def load_trace_dir(log_dir: str) -> dict:
    """The parsed form of the one ``*.xplane.pb`` a trace wrote."""
    files = sorted(glob.glob(str(pathlib.Path(log_dir) / "**"
                                 / "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {log_dir}")
    return parse_xspace(pathlib.Path(files[-1]).read_bytes())


# ---------------------------------------------------------------------------
# shared reductions
# ---------------------------------------------------------------------------

def window(parsed: dict) -> Tuple[float, float]:
    """(start_ns, end_ns) of the traced window: the first batch hand-over
    to the end of the last step callback."""
    starts = [e[0] for e in parsed["host"] if e[2] == BATCH_SPAN]
    ends = [e[0] + e[1] for e in parsed["host"] if e[2] == CALLBACK_SPAN]
    if not starts or not ends:
        raise ValueError("the trace holds no bench/batch or bench/callback "
                         "annotation")
    return min(starts), max(ends)


def _clip(s: float, d: float, lo: float, hi: float) -> float:
    return max(0.0, min(s + d, hi) - max(s, lo))


def union_ns(intervals: List[Tuple[float, float]]) -> float:
    """Length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_ns(parsed: dict) -> float:
    """Device busy time inside the window, averaged over the chips: the
    union of the intervals in which an operation ran."""
    lo, hi = window(parsed)
    iv = [(max(s, lo), min(s + d, hi)) for s, d, *_ in parsed["ops"]
          if s + d > lo and s < hi]
    return union_ns(iv) / max(1, parsed["chips"])


def steps_in_window(parsed: dict) -> List[list]:
    """The program executions (training steps) that start in the window."""
    lo, hi = window(parsed)
    return [m for m in parsed["modules"] if lo <= m[0] < hi]


def scope_ns(parsed: dict, keep) -> float:
    """Device time, summed over the chips, of the ops in the window whose
    scope ``keep(scope)`` accepts."""
    lo, hi = window(parsed)
    return sum(_clip(op[0], op[6], lo, hi) for op in parsed["ops"]
               if keep(op[2]))


def per_step_scope_ns(parsed: dict, keep) -> List[float]:
    """For each step in the window, the device time of its ops that
    ``keep`` accepts (ops are assigned to the execution they fall in)."""
    out = []
    steps = steps_in_window(parsed)
    ops = parsed["ops"]
    i = 0
    for s, d, *_ in steps:
        while i < len(ops) and ops[i][0] < s:
            i += 1
        t, j = 0.0, i
        while j < len(ops) and ops[j][0] < s + d:
            if keep(ops[j][2]):
                t += _clip(ops[j][0], ops[j][6], s, s + d)
            j += 1
        out.append(t)
    return out


def is_kfac(scope: str) -> bool:
    return "kfac/" in scope


def is_factor(scope: str) -> bool:
    return "kfac/factor/" in scope


def is_precond(scope: str) -> bool:
    return "kfac/precond/" in scope


def is_light_brand(scope: str) -> bool:
    return "kfac/factor/" in scope and "/light_brand/" in scope


def _op_key(scope: str) -> str:
    """A readable group for an op: its kfac path down to the work kind
    (``kfac/factor/b3_brand/light_brand``), else its name-stack path
    without the jit prefix and the trailing ``:``."""
    parts = [p for p in scope.rstrip(":").split("/") if p]
    if parts and parts[0].startswith("jit("):
        parts = parts[1:]
    if "kfac" in parts:
        k = parts.index("kfac")
        depth = 4 if k + 1 < len(parts) and parts[k + 1] == "factor" else 3
        return "/".join(parts[k:k + depth])
    return "/".join(parts) or scope


def breakdown(parsed: dict, n: int = 10) -> Dict[str, list]:
    """The device ops that took most time, grouped by ``_op_key``, and
    the idle gaps of the device inside the window, grouped by the
    innermost host event that covers each gap's middle."""
    lo, hi = window(parsed)
    by_op: Dict[str, float] = {}
    iv = []
    for s, d, scope, _, _, _, own in parsed["ops"]:
        if _clip(s, d, lo, hi) > 0:
            key = _op_key(scope)
            by_op[key] = by_op.get(key, 0.0) + _clip(s, own, lo, hi) / 1e9
            iv.append((max(s, lo), min(s + d, hi)))
    gaps = []
    cur = lo
    for s, e in sorted(iv):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    host = parsed["host"]
    by_gap: Dict[str, float] = {}
    for s, e in gaps:
        mid = 0.5 * (s + e)
        label, best = "no host event", None
        for hs, hd, name in host:
            if hs > mid:
                break
            if hs + hd >= mid and (best is None or hd < best):
                label, best = name, hd
        by_gap[label] = by_gap.get(label, 0.0) + (e - s) / 1e9
    top = lambda d: [[k, v] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:n]]
    return {"device_ops": top(by_op), "idle_gaps": top(by_gap)}


def summary(parsed: dict) -> dict:
    """``busy_s``, ``window_s`` and the step count of the window."""
    lo, hi = window(parsed)
    return {"busy_s": busy_ns(parsed) / 1e9, "window_s": (hi - lo) / 1e9,
            "steps": len(steps_in_window(parsed))}
