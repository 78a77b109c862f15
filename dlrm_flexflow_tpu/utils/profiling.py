"""Profiling hooks.

Parity with the reference's two profiling layers (SURVEY.md §5.1):
- per-op timing under `--profiling` (reference FFConfig::profiling →
  cudaEvent timing + prints inside fwd/bwd tasks, linear.cu:499-531,
  embedding.cu:257-262): here each op's compiled XLA subgraph is timed on
  the real device (CostModel.measure_op — the same machinery the strategy
  search calibrates with) and reported as a table, plus a roofline estimate
  so kernel-vs-model gaps are visible.
- whole-run tracing (reference Legion Prof via -lg:prof): here
  `jax.profiler.trace(dir)` captures an xprof/TensorBoard trace of the
  jitted train step — set FFConfig.profile_dir (CLI `--profile-dir`)
  before calling fit(). The trace carries the program's own names: the
  host spans of obs/trace.py (`train/dispatch`, `fit/throttle`, ...) and,
  on every device op, the `ff.<op name>` scope it was traced under.

Per-iteration trace *replay* (reference begin_trace/end_trace(111),
dlrm.cc:179-185) needs no hook: jit compile-once/execute-many subsumes it.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple


def profile_ops(model, measure: bool = True) -> List[Dict]:
    """Per-op profile of `model` (must be compiled): measured fwd time of
    each op's compiled subgraph at its strategy's shard shape, plus the
    roofline estimate and FLOPs. Returns a list of row dicts, heaviest
    first."""
    from ..core.op import InputOp
    from ..search.cost_model import CostModel

    cm = CostModel(compute_dtype=model.compute_dtype, measure=measure)
    rows = []
    for op in model.ops:
        if isinstance(op, InputOp):
            continue
        pc = model._op_pc.get(op.name) if hasattr(model, "_op_pc") else None
        if pc is None:
            continue
        est = cm.op_compute_time(op, pc)
        meas = cm.measure_op(op, pc) if measure else None
        batch = op.outputs[0].shape[0] if op.outputs[0].num_dims else 1
        rows.append({
            "op": op.name,
            "type": type(op).__name__,
            "degrees": tuple(pc.degrees),
            "flops": op.flops_per_sample() * batch / max(pc.num_parts, 1),
            "roofline_ms": est * 1e3,
            "measured_ms": None if meas is None else meas * 1e3,
        })
    rows.sort(key=lambda r: -(r["measured_ms"] or r["roofline_ms"]))
    return rows


def format_profile(rows: List[Dict]) -> str:
    head = (f"{'op':<28}{'type':<14}{'degrees':<12}"
            f"{'measured_ms':>12}{'roofline_ms':>13}{'GFLOP':>9}")
    lines = [head, "-" * len(head)]
    for r in rows:
        meas = ("-" if r["measured_ms"] is None
                else f"{r['measured_ms']:.4f}")
        lines.append(
            f"{r['op']:<28}{r['type']:<14}{str(r['degrees']):<12}"
            f"{meas:>12}{r['roofline_ms']:>13.4f}"
            f"{r['flops'] / 1e9:>9.3f}")
    return "\n".join(lines)


class TraceContext:
    """jax.profiler.trace wrapper that no-ops when dir is empty."""

    def __init__(self, profile_dir: Optional[str]):
        self.profile_dir = profile_dir
        self._cm = None

    def __enter__(self):
        if self.profile_dir:
            import jax
            self._cm = jax.profiler.trace(self.profile_dir)
            self._cm.__enter__()
        return self

    def __exit__(self, *exc):
        if self._cm is not None:
            return self._cm.__exit__(*exc)
        return False
