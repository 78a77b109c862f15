"""From the profiler's trace to numbers: the reduction every per-layer
metric reads, kept with the benchmark so that every PR computes the same
number the same way.

Two steps, so that the arithmetic can be checked on a small recorded trace
(tests/perfbench/data/) without the profiler:

  load_xplane(path)   `.xplane.pb` -> a plain dict ("recorded trace"):
                      per device the op events and the program launches, the
                      host thread that carried the harness's annotations, and
                      the traced slice as that thread's `SLICE` annotation
  reduce(rec, steps)  recorded trace -> seconds per class, busy union, idle
                      share, exposed collective time, launches a step, the
                      breakdown

Times in a recorded trace are nanoseconds on the profiler's clock, which
host and device planes share. On the TPU an op event's name is the whole
HLO instruction (`%fusion.3 = f32[...] fusion(...)`); an op goes by the
instruction's name and keeps the text as its detail. Device ops fall into
three classes, by what the trace says itself (no scope of the program's is
needed): `collective` (HLO collective ops), `mosaic` (Pallas kernels: custom
calls to `tpu_custom_call`; with no `name=` they are called after the jitted
function, `train_step.2`), `xla` (everything else XLA compiled).
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

SLICE = "perfbench/slice"
# the lines of a device's plane that are read; on the async line an
# `<op>-start` and its `-done` are one event
LINES = {"XLA Ops": "ops", "XLA Modules": "programs",
         "Async XLA Ops": "async_ops"}
LONGEST_GAPS = 256
DETAIL_CHARS = 600

_COLLECTIVES = (r"(all-to-all|all-reduce|all-gather|reduce-scatter|"
                r"collective-permute|collective-broadcast|ragged-all-to-all|"
                r"send|recv)")
_COLLECTIVE = re.compile(rf"^{_COLLECTIVES}(-start|-done)?(\.\d+)*$")
_ASYNC = re.compile(r"^(?P<op>.*?)-(?P<end>start|done)(?P<tail>(\.\d+)*)$")
_MOSAIC = re.compile(r"tpu_custom_call|mosaic|pallas", re.I)

Interval = Tuple[float, float]


def classify(name: str, detail: str = "") -> str:
    if _COLLECTIVE.match(name):
        return "collective"
    # not every custom call is a kernel: XLA's own `ConcatBitcast` is one too
    if _MOSAIC.search(name) or _MOSAIC.search(detail):
        return "mosaic"
    return "xla"


def split_hlo(text: str) -> Tuple[str, str]:
    """An op event's name -> (instruction name, the rest)."""
    head, _, rest = text.partition(" = ")
    return head.lstrip("%"), rest


# --------------------------------------------------------------------------
# interval arithmetic
# --------------------------------------------------------------------------
def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def measure(merged: Sequence[Interval]) -> float:
    return sum(b - a for a, b in merged)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The part of merged `a` that merged `b` does not cover."""
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k, cur = j, lo
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def _clip(events, lo: float, hi: float) -> List[Tuple[str, float, float]]:
    out = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((name, a, b))
    return out


def collective_spans(ops: Sequence[Tuple[str, float, float]],
                     async_ops: Sequence[Tuple[str, float, float]] = ()
                     ) -> List[Interval]:
    """The time each collective is in flight: a synchronous one while its
    op runs, an asynchronous one from the start of `<op>-start` to the end
    of its `<op>-done`, which the trace gives as one event of its async line
    or as two ops (paired here in order by the rest of the name)."""
    spans = [(a, b) for name, a, b in async_ops
             if classify(name) == "collective"]
    open_ = defaultdict(list)
    for name, a, b in sorted(ops, key=lambda e: e[1]):
        if classify(name) != "collective":
            continue
        m = _ASYNC.match(name)
        if not m:
            spans.append((a, b))
        elif m["end"] == "start":
            open_[m["op"] + m["tail"]].append((a, b))
        else:
            began = open_[m["op"] + m["tail"]]
            spans.append((began.pop(0)[0] if began else a, b))
    spans.extend(s for began in open_.values() for s in began)
    return spans


# --------------------------------------------------------------------------
# recorded trace -> numbers
# --------------------------------------------------------------------------
def reduce(rec: dict, steps: int) -> Optional[dict]:
    """None where no device op ran inside the slice."""
    lo, hi = rec["window"]
    window = (hi - lo) * 1e-9
    details = rec.get("details", {})
    per_device = []
    for dev in sorted(rec["devices"]):
        ops = _clip(rec["devices"][dev]["ops"], lo, hi)
        if not ops:
            continue
        klass, by_name = defaultdict(list), defaultdict(float)
        for name, a, b in ops:
            klass[classify(name, details.get(name, ""))].append((name, a, b))
            by_name[name] += (b - a) * 1e-9
        busy = union([(a, b) for _, a, b in ops])
        others = union([(a, b) for k in ("xla", "mosaic")
                        for _, a, b in klass[k]])
        in_flight = union(collective_spans(
            klass["collective"],
            _clip(rec["devices"][dev].get("async_ops", []), lo, hi)))
        per_device.append({
            "busy_s": measure(busy) * 1e-9,
            "xla_s": sum(b - a for _, a, b in klass["xla"]) * 1e-9,
            "mosaic_s": sum(b - a for _, a, b in klass["mosaic"]) * 1e-9,
            "collective_s": measure(in_flight) * 1e-9,
            "collective_exposed_s":
                measure(subtract(in_flight, others)) * 1e-9,
            "programs": sum(1 for _, start, _ in
                            rec["devices"][dev]["programs"]
                            if lo <= start < hi),
            "by_name": by_name,
            "gaps": subtract([(lo, hi)], busy),
        })
    if not per_device or window <= 0:
        return None
    n = len(per_device)
    out = {"steps": int(steps), "window_s": window, "devices": n}
    for key in ("busy_s", "xla_s", "mosaic_s", "collective_s",
                "collective_exposed_s", "programs"):
        out[key] = sum(d[key] for d in per_device) / n
    out["idle_share"] = 1.0 - out["busy_s"] / window
    names = defaultdict(float)
    for d in per_device:
        for name, s in d["by_name"].items():
            names[name] += s / n
    out["breakdown"] = {
        "device_ops": [[_label(k, details.get(k, "")), v] for k, v in sorted(
            names.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": _gaps_by_host(per_device[0]["gaps"],
                                   rec.get("host", []))[:10],
    }
    return out


def _label(name: str, detail: str) -> str:
    """`fusion.3 [xla] f32[89856,128]{...}`: the op, its class, what it
    makes."""
    made = detail.split(" ", 1)[0][:48]
    return f"{name} [{classify(name, detail)}] {made}".rstrip()


def _gaps_by_host(gaps: Sequence[Interval], host) -> List[list]:
    """The longest idle gaps of the first device, summed by what the
    annotated host thread was doing at the middle of each: the innermost
    event there.
    Until the program carries spans of its own, that is mostly the harness's
    slice annotation, i.e. "somewhere inside fit"."""
    total = defaultdict(float)
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:LONGEST_GAPS]:
        mid, best = (a + b) / 2, None
        for name, start, dur in host:
            if start <= mid < start + dur and (best is None
                                               or dur < best[1]):
                best = (name, dur)
        total[best[0] if best else "(no host span)"] += (b - a) * 1e-9
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])]


# --------------------------------------------------------------------------
# .xplane.pb -> recorded trace
# --------------------------------------------------------------------------
def load_xplane(path: str) -> Optional[dict]:
    """None where the trace holds no `SLICE` annotation."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, dict] = {}
    details: Dict[str, str] = {}
    window, host = None, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            dev = devices.setdefault(plane.name, {"ops": [], "programs": []})
            for line in plane.lines:
                key = LINES.get(line.name)
                if key is None:
                    continue
                for e in line.events:
                    name, rest = split_hlo(e.name)
                    dev.setdefault(key, []).append(
                        [name, e.start_ns, e.duration_ns])
                    if key == "ops" and name not in details:
                        details[name] = rest[:DETAIL_CHARS]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = [[e.name, e.start_ns, e.duration_ns]
                          for e in line.events]
                for name, start, dur in events:
                    if name == SLICE:       # the thread the harness ran on
                        window = [start, start + dur]
                        host = [e for e in events if e[2] > 0]
    if window is None:
        return None
    return {"window": window, "devices": devices or _host_as_devices(data),
            "host": host, "details": details}


def _host_as_devices(data) -> Dict[str, dict]:
    """A CPU rehearsal has no device plane: XLA's CPU client reports its ops
    on host threads, one launch a `run_id`. Never a device number, only a way
    to walk the same code without the chip."""
    devices: Dict[str, dict] = {}
    runs: Dict[str, dict] = defaultdict(dict)
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                stats = {str(k): v for k, v in e.stats}
                if "hlo_op" not in stats:
                    continue
                dev = f"/host-as-device:{stats.get('device_ordinal', 0)}"
                a, b = e.start_ns, e.start_ns + e.duration_ns
                devices.setdefault(dev, {"ops": [], "programs": []})[
                    "ops"].append([e.name, a, b - a])
                run = runs[dev].setdefault(stats.get("run_id"), [a, b])
                run[0], run[1] = min(run[0], a), max(run[1], b)
    for dev, launched in runs.items():
        devices[dev]["programs"] = [["run", a, b - a]
                                    for a, b in launched.values()]
    return devices
