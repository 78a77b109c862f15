"""Structured tracing: named spans on the profiler's clock and, with
``--obs on``, in a bounded in-memory ring exported as Chrome-trace /
Perfetto JSON; and the record of the compiled step programs, with what
each can be asked (its scope map, the memory the compiler counts for it).

``utils/profiling.py`` covers the two reference layers (per-op timing,
whole-run xprof capture); what neither shows is the CROSS-SUBSYSTEM
story — where a request spent its time between the prefetch ring, the
superstep dispatch, the delta publisher, the snapshot watcher, and the
serving batcher. This module instruments those seams:

- training: ``prefetch/produce`` → ``train/dispatch`` around
  ``train/step`` / ``train/superstep``; in ``fit()``'s loop
  ``fit/stage``, ``fit/throttle``, ``fit/epoch_end``, ``fit/drain``;
  ``compile/<kind>`` where a step program is built or loaded
- serving:  ``serve/enqueue`` → ``serve/batch-form`` →
  ``serve/dispatch`` → ``serve/swap``
- freshness: ``publish/full`` / ``publish/delta`` →
  ``publish/watcher-apply`` → ``serve/swap``

Events land in a bounded ring (oldest overwritten — a long-lived server
cannot leak; ``dropped()`` counts the overwritten tail) and are tagged
with the emitting thread, so the existing ``ff-*`` thread-naming
discipline (flexcheck FLX101) becomes the trace's lane structure for
free. :func:`chrome_trace` renders the ring as Chrome's trace-event
JSON — load it at ``chrome://tracing`` or https://ui.perfetto.dev —
with complete ("X") events whose ts/dur nesting reconstructs the span
tree per thread.

:func:`span` is the program's one span call. It always is a
``jax.profiler.TraceAnnotation``, so a profiler session (``--profile-dir``,
the benchmark's traced slice) sees the program's spans beside JAX's own
events and the device's ops, on one clock; without a session the
annotation is inert (a third of a microsecond). With ``--obs on`` the span
also lands in the ring. Off (the default), :func:`instant` and
:func:`complete` return immediately and the ring stays empty.

The device half of the story is not a span: every op of the model is
traced under a ``jax.named_scope("ff.<op name>")`` (core/model.py), which
XLA keeps as each instruction's ``op_name``. :func:`program_scopes` reads
that back from the newest compiled step programs, so "what is
``fusion.7``" has an answer: ``jit(train_step)/.../ff.update.emb/dedup/sort``.

Every step executable ``FFModel._cached_compile`` builds or loads leaves
one record (:func:`programs`: the executable, the seconds lowering and
compiling took, built or loaded). :func:`program_memory` answers from it
what the runtime's ``peak_bytes_in_use`` does not show: the bytes of HBM
the compiler counts for a step, temporaries included.
"""

from __future__ import annotations

import functools
import json
import os
import re
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

from jax.profiler import TraceAnnotation

from ..utils.logging import get_logger

_ENABLED = False
_TRACE_DIR = ""
_CAPACITY = 65536

# the ring: plain deque — append on a maxlen deque is GIL-atomic, so
# emitters never take a lock; exporters snapshot with list(_RING)
_RING: "deque[Dict[str, Any]]" = deque(maxlen=_CAPACITY)
_APPENDED = 0                      # lifetime events (dropped = this - len)
_THREAD_NAMES: Dict[int, str] = {}  # tid -> last seen thread name
_PID = os.getpid()


def enabled() -> bool:
    return _ENABLED


def set_enabled(on: bool) -> None:
    global _ENABLED
    _ENABLED = bool(on)


def set_trace_dir(path: str) -> None:
    global _TRACE_DIR
    _TRACE_DIR = str(path or "")


def trace_dir() -> str:
    return _TRACE_DIR


def set_capacity(n: int) -> None:
    """Resize the ring (keeps the newest events)."""
    global _RING, _CAPACITY
    if n < 1:
        raise ValueError(f"trace ring capacity must be >= 1, got {n}")
    _CAPACITY = int(n)
    _RING = deque(_RING, maxlen=_CAPACITY)


def clear() -> None:
    global _APPENDED
    _RING.clear()
    _THREAD_NAMES.clear()
    _APPENDED = 0


def events() -> List[Dict[str, Any]]:
    return list(_RING)


def dropped() -> int:
    """Events overwritten by the ring so far."""
    return max(0, _APPENDED - len(_RING))


def override(on: bool, trace_dir: Optional[str] = None,
             capacity: Optional[int] = None):
    """Context manager flipping tracing for tests; restores the ring
    contents, capacity, and trace dir on exit."""
    import contextlib

    @contextlib.contextmanager
    def _scope():
        global _ENABLED, _TRACE_DIR
        prev = (_ENABLED, _TRACE_DIR, _CAPACITY)
        _ENABLED = bool(on)
        if trace_dir is not None:
            _TRACE_DIR = trace_dir
        if capacity is not None:
            set_capacity(capacity)
        try:
            yield
        finally:
            _ENABLED, _TRACE_DIR, cap = prev
            set_capacity(cap)

    return _scope()


def _now_us() -> float:
    return time.perf_counter() * 1e6


def _emit(ev: Dict[str, Any]) -> None:
    global _APPENDED
    t = threading.current_thread()
    tid = t.ident or 0
    _THREAD_NAMES[tid] = t.name
    ev["pid"] = _PID
    ev["tid"] = tid
    _RING.append(ev)
    _APPENDED += 1


class Span:
    """One named duration, on both clocks: a profiler annotation while it
    is open and a complete ("X") event in the ring on exit, so an
    abandoned span (thread died mid-work) simply never lands — the
    instants around it still tell the story."""

    __slots__ = ("name", "cat", "args", "_t0", "_ann")

    def __init__(self, name: str, cat: str, args: Dict[str, Any]):
        self.name = name
        self.cat = cat
        self.args = args
        self._t0 = 0.0
        self._ann = TraceAnnotation(name, **args)

    def __enter__(self) -> "Span":
        self._ann.__enter__()
        self._t0 = _now_us()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = _now_us()
        self._ann.__exit__(exc_type, exc, tb)
        args = self.args
        if exc_type is not None:
            args = dict(args)
            args["error"] = exc_type.__name__
        _emit({"name": self.name, "cat": self.cat or "ff", "ph": "X",
               "ts": self._t0, "dur": t1 - self._t0, "args": args})
        return False


def span(name: str, cat: str = "", **args):
    """Context manager timing one named unit of work: a profiler
    annotation always (inert without a profiler session), and an event in
    the ring as well when tracing is on. Spans that run once a step pass
    no keyword arguments."""
    if not _ENABLED:
        return TraceAnnotation(name, **args)
    return Span(name, cat, args)


def spanned(name: str):
    """Decorator form of :func:`span`: a function's whole body."""
    def wrap(fn):
        @functools.wraps(fn)
        def run(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return run
    return wrap


def complete(name: str, t0_s: float, cat: str = "", **args) -> None:
    """Record an already-timed duration: ``t0_s`` is the
    ``time.perf_counter()`` reading at its start. For call sites that
    cannot wrap their work in a ``with`` (a batch formed across a
    condition-variable wait, say)."""
    if not _ENABLED:
        return
    t0 = t0_s * 1e6
    _emit({"name": name, "cat": cat or "ff", "ph": "X", "ts": t0,
           "dur": _now_us() - t0, "args": args})


def instant(name: str, cat: str = "", **args) -> None:
    """Record a zero-duration marker (stall reports, anomaly sentinel
    fires, autoscaler decisions, drift warnings): visible even when the
    subsystem that emitted it is wedged and will never close a span."""
    if not _ENABLED:
        return
    _emit({"name": name, "cat": cat or "ff", "ph": "i", "s": "t",
           "ts": _now_us(), "args": args})


# ---------------------------------------------------------------------
# the record of the step programs, and what a program can be asked: which
# op of the model an instruction is, which outputs alias an input, the
# memory the compiler counts
# ---------------------------------------------------------------------
# one record a step executable `_cached_compile` handed over, built or
# loaded: newest last, one a (kind, key), the oldest out past the bound (an
# elastic job that recompiles for ever must not pin executables). A kind is
# one jitted function (train -> jit_train_step), a key the batch signature
# it was built for. Nothing is read from an executable until someone asks,
# so a program that can give neither its text nor its memory analysis
# costs a compile nothing
class Program(NamedTuple):
    kind: str
    key: Any
    executable: Any
    lower_s: float       # tracing and lowering; 0 for a loaded program
    compile_s: float     # `.compile()`, or the CompileCache's load
    loaded: bool         # the model's own CompileCache handed it over


PROGRAMS_KEPT = 32
_PROGRAMS: "OrderedDict[Tuple[str, Any], Program]" = OrderedDict()
_PROGRAMS_LOCK = threading.Lock()
_MODULE = re.compile(r"HloModule ([^\s,]+)")
_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%?([\w.\-]+) = (?:.*\bop_name="([^"]*)")?')


def note_program(kind: str, executable, key: Any = None,
                 lower_s: float = 0.0, compile_s: float = 0.0,
                 loaded: bool = False) -> None:
    """Keep one record of a compiled program: one small store a compile."""
    rec = Program(kind, key, executable, lower_s, compile_s, loaded)
    with _PROGRAMS_LOCK:
        _PROGRAMS.pop((kind, key), None)
        _PROGRAMS[(kind, key)] = rec
        while len(_PROGRAMS) > PROGRAMS_KEPT:
            _PROGRAMS.popitem(last=False)


def programs(kind: Optional[str] = None) -> List[Program]:
    """The records, oldest first; of one kind where one is named."""
    with _PROGRAMS_LOCK:
        recs = list(_PROGRAMS.values())
    return [r for r in recs if kind is None or r.kind == kind]


_ALIASED_OUTPUT = re.compile(r"\{([\d, ]*)\}: \((\d+), \{[\d, ]*\}")


def aliased_outputs(executable) -> Optional[Dict[str, int]]:
    """{output index: parameter number} of a compiled program's alias
    table, the first line of its text (`input_output_alias={ {0}: (0, {},
    may-alias), ... }`): the outputs that take over an input's buffer. An
    index as the text writes it ("11"; "" for a result that is no tuple).
    None for an executable that gives no text (see `program_scopes`)."""
    try:
        header = executable.as_text().split("\n", 1)[0]
    except Exception:   # noqa: BLE001 - a diagnostic, never fatal
        return None
    return {out: int(param)
            for out, param in _ALIASED_OUTPUT.findall(header)}


def fresh_outputs(executable) -> Optional[int]:
    """How many of a compiled program's outputs take over no input's
    buffer. The runtime allocates a device buffer for each before the
    call returns, which is what a short step's dispatch waits for
    (ROADMAP S1): a step program should read 1, its metrics vector."""
    aliased = aliased_outputs(executable)
    return (None if aliased is None
            else executable.out_tree.num_leaves - len(aliased))


def hlo_scopes(hlo_text: str) -> Dict[str, str]:
    """{instruction name: op_name path} of one optimized HLO module's
    text ("" for an instruction that carries none). The trace calls a
    device op by the same instruction name (`fusion.7`, `sort.0`)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            out[m.group(1)] = m.group(2) or ""
    return out


def program_scopes() -> Dict[str, Dict[str, str]]:
    """{module name: {instruction name: op_name path}} of the newest noted
    program of each kind, e.g.
    ``program_scopes()["jit_train_step"]["fusion.7"]`` ->
    ``"jit(train_step)/jit(main)/ff.update.emb/dedup/sort"``. Parsed from
    the executables' text on request only."""
    out = {}
    newest = {rec.kind: rec.executable for rec in programs()}
    for kind, executable in newest.items():
        try:
            text = executable.as_text()
        except Exception as e:   # noqa: BLE001 - a diagnostic, never fatal
            # (a backend may keep no HLO for an executable it loaded)
            get_logger("obs").warning(
                "program_scopes: the %s executable gives no text (%s: %s)",
                kind, type(e).__name__, e)
            continue
        module = _MODULE.match(text)
        if module:
            out[module.group(1)] = hlo_scopes(text)
    return out


MEMORY_PARTS = ("argument", "output", "alias", "temp", "generated_code")


def program_memory(executable) -> Optional[Dict[str, int]]:
    """Bytes of device memory a compiled program needs, by the compiler's
    own count (`executable.memory_analysis()`): its arguments, its outputs,
    the part of the outputs that takes over an argument's buffer (`alias`),
    the temporaries XLA's schedule keeps live at once (`temp`), the code,
    and `counted` = argument + output - alias + temp: what must be free on
    the chip for the program to run. The runtime's `peak_bytes_in_use`
    leaves the temporaries out. None for an executable that gives no
    analysis (a deserialized one may not); read on request only."""
    try:
        ma = executable.memory_analysis()
        out = {part: int(getattr(ma, f"{part}_size_in_bytes"))
               for part in MEMORY_PARTS}
    except Exception:   # noqa: BLE001 - a diagnostic, never fatal
        return None
    out["counted"] = (out["argument"] + out["output"] - out["alias"]
                      + out["temp"])
    return out


STEP_KINDS = ("train", "superstep")


def collect_step_programs():
    """Registry collector (obs.metrics): what the newest step program of
    each kind needs and what the step programs cost to build, read from
    the records at scrape time."""
    for kind in STEP_KINDS:
        recs = programs(kind)
        if not recs:
            continue
        memory = program_memory(recs[-1].executable)
        for part, n in (memory or {}).items():
            yield "ff_step_hbm_bytes", {"kind": kind, "part": part}, n
        yield ("ff_step_compile_seconds", {"kind": kind, "phase": "lower"},
               sum(r.lower_s for r in recs))
        yield ("ff_step_compile_seconds", {"kind": kind, "phase": "compile"},
               sum(r.compile_s for r in recs))
        yield ("ff_step_programs_loaded_total", {"kind": kind},
               sum(r.loaded for r in recs))


# ---------------------------------------------------------------------
# export
# ---------------------------------------------------------------------
def chrome_trace() -> Dict[str, Any]:
    """The ring as a Chrome trace-event JSON object: thread-name
    metadata first (so Perfetto labels each lane with the ff-* worker
    name), then the events oldest-first."""
    evs = list(_RING)
    meta = [{"name": "thread_name", "ph": "M", "pid": _PID, "tid": tid,
             "args": {"name": name}}
            for tid, name in sorted(_THREAD_NAMES.items())]
    return {
        "traceEvents": meta + evs,
        "displayTimeUnit": "ms",
        "otherData": {
            "producer": "dlrm_flexflow_tpu.obs.trace",
            "dropped_events": dropped(),
        },
    }


def export(path: str) -> str:
    """Write the current ring as Chrome-trace JSON to ``path``."""
    tmp = f"{path}.tmp-{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(chrome_trace(), f)
    os.replace(tmp, path)
    return path


def export_to_dir(directory: Optional[str] = None) -> Optional[str]:
    """Export to the configured ``--obs-trace-dir`` (or an explicit
    directory); None when neither is set. File names are unique per
    (pid, monotonic-ns) so concurrent exporters never clobber."""
    d = directory or _TRACE_DIR
    if not d:
        return None
    os.makedirs(d, exist_ok=True)
    name = f"ff-trace-{_PID}-{time.monotonic_ns()}.json"
    return export(os.path.join(d, name))
