"""From the names the program gives its work to numbers: device time by
the program's scopes, host time by the program's spans, and the device's
idle time by the span the host was in. Arithmetic on a recorded trace
(tracereduce.load_xplane's dict) only, so it is checked on small recorded
traces without the profiler.

Two things the program writes are read here, and nothing else of it:

  scopes  every op of the model is traced under `jax.named_scope("ff.<op
          name>")`, which XLA keeps as each instruction's `op_name`
          (`jit(train_step)/.../transpose(jvp(ff.top_dense_1))/dot_general`).
          The trace calls a device op by its instruction's name
          (`fusion.7`); `rec["scopes"]` = {module name: {instruction name:
          op_name path}} is `dlrm_flexflow_tpu.obs.trace.program_scopes()`,
          read from the step executable itself (spanreport.py puts it into
          the recorded trace a run kept).
  spans   `obs.trace.span(name)` is a `jax.profiler.TraceAnnotation`, so
          the spans of `fit()`'s loop (`train/dispatch`, `train/step`,
          `fit/throttle`, ...) are events of the thread that ran the
          slice, on the clock the device's ops are on.

A program that has neither (a commit before the names) gives a recorded
trace with no scopes and no program span; spanreport.py's metrics are then
left out.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, Iterator, List, Tuple

from perfbench.tracereduce import _clip, classify, subtract, union

# what the program's spans are called (obs/trace.py's module docstring)
SPAN_PREFIXES = ("train/", "fit/", "compile/", "prefetch/")
_FINGERPRINT = re.compile(r"\(\d+\)$")
_FF = re.compile(r"ff\.([^/()]+)")


def _first_device(rec: dict) -> dict:
    """The first device with an op inside the slice, as reduce() takes
    it for the breakdown."""
    lo, hi = rec["window"]
    for dev in sorted(rec["devices"]):
        if _clip(rec["devices"][dev]["ops"], lo, hi):
            return rec["devices"][dev]
    return {"ops": [], "programs": []}


def scope_map(rec: dict) -> Dict[str, str]:
    """{instruction name: scope path} of the module(s) the `XLA Modules`
    line names inside the slice, its `(fingerprint)` stripped. A CPU
    rehearsal's launches carry no module name: there, every noted map."""
    scopes = rec.get("scopes") or {}
    lo, hi = rec["window"]
    launched = {_FINGERPRINT.sub("", name) for name, _, _ in
                _clip(_first_device(rec)["programs"], lo, hi)}
    merged: Dict[str, str] = {}
    for module in sorted(launched & set(scopes)) or sorted(scopes):
        merged.update(scopes[module])
    return merged


def device_ops(rec: dict) -> Iterator[Tuple[str, str, str, float]]:
    """(instruction name, scope path, class, seconds inside the slice) of
    each compute op of the first device; collectives have metrics of
    their own (tracereduce)."""
    lo, hi = rec["window"]
    paths, details = scope_map(rec), rec.get("details", {})
    for name, a, b in _clip(_first_device(rec)["ops"], lo, hi):
        klass = classify(name, details.get(name, ""))
        if klass != "collective":
            yield name, paths.get(name, ""), klass, (b - a) * 1e-9


def device_by_scope(rec: dict) -> Dict[str, float]:
    """{scope path: seconds}, "" for ops the map has no path for."""
    out: Dict[str, float] = defaultdict(float)
    for _, path, _, s in device_ops(rec):
        out[path] += s
    return dict(out)


def group(path: str) -> str:
    """The model's op a path belongs to: its outermost `ff.` component,
    `jvp(` / `transpose(` / `vmap(` peeled off and the marker dropped
    (`.../transpose(jvp(ff.top_dense_1))/mul` -> `top_dense_1`,
    `.../ff.update.emb/dedup/sort` -> `update.emb`); "" where the path
    has none."""
    m = _FF.search(path)
    return m.group(1) if m else ""


def _span_events(rec: dict) -> List[Tuple[str, float, float]]:
    """The slice thread's events that are the program's spans, clipped to
    the slice; a span's keyword arguments are not part of its name."""
    lo, hi = rec["window"]
    return [(name.split("#")[0], a, b)
            for name, a, b in _clip(rec.get("host", []), lo, hi)
            if name.startswith(SPAN_PREFIXES)]


def host_by_span(rec: dict) -> Dict[str, float]:
    """{span name: seconds the slice's thread spent in it}; a nested span
    counts under its own name and under the one around it."""
    out: Dict[str, float] = defaultdict(float)
    for name, a, b in _span_events(rec):
        out[name] += (b - a) * 1e-9
    return dict(out)


def idle_by_span(rec: dict) -> Dict[str, float]:
    """Every idle gap of the first device, put down to the outermost
    program span that holds its middle, "" where none does: {span name:
    seconds}. The values sum to the slice minus the device's busy time."""
    lo, hi = rec["window"]
    busy = union([(a, b) for _, a, b in
                  _clip(_first_device(rec)["ops"], lo, hi)])
    # outermost spans: on one thread spans nest, so one that starts
    # before the last outermost one has ended lies inside it
    outer: List[Tuple[float, float, str]] = []
    for name, a, b in sorted(_span_events(rec), key=lambda e: (e[1], -e[2])):
        if not outer or a >= outer[-1][1]:
            outer.append((a, b, name))
    starts = [a for a, _, _ in outer]
    out: Dict[str, float] = defaultdict(float)
    for a, b in subtract([(lo, hi)], busy):
        mid = (a + b) / 2
        i = bisect.bisect_right(starts, mid) - 1
        inside = i >= 0 and mid < outer[i][1]
        out[outer[i][2] if inside else ""] += (b - a) * 1e-9
    return dict(out)

