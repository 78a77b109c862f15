"""What the host's threads did inside a traced slice, by the profiler's own
event names: a kept `.xplane.pb` (`perfbench/run.py --trace 1 --keep-trace
DIR`, `perfbench/spanreport.py --keep-trace DIR`) read for the calls the
runtime makes under a dispatch (`PJRT_LoadedExecutable_Execute`,
`DeferredTpuAllocator::Allocate`, `ParseArguments`, ...), which the recorded
`trace.json.gz` leaves out: it keeps the harness's thread alone.

    python benchmarks/host_calls.py DIR/*.xplane.pb [--match Allocate] \\
        [--steps 200] [--top 25] [--threads]

One line a name, heaviest first: calls, calls a step, us a call, us a step,
inside the harness's `perfbench/slice` annotation (the whole trace where
there is none). `--steps` is the slice's step count (`steps` in
`trace.json.gz`; counted from the `train/step` and `train/superstep` spans
when left out); `--threads` puts the thread's name before each event's, which
tells the dispatching thread's calls from the runtime's own threads'. The
last line is the same as one JSON object. This is how
ROADMAP S1's "five allocations a dispatch" was counted (PERF.md, PR 33).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench.tracereduce import SLICE   # noqa: E402  the harness's annotation

STEP_SPANS = ("train/step", "train/superstep")


def host_events(path: str, threads: bool = False):
    """[(name, start_ns, duration_ns)] of every host thread, and the
    slice's (start, end) or None."""
    from jax.profiler import ProfileData
    events, window = [], None
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == SLICE:
                    window = (e.start_ns, e.start_ns + e.duration_ns)
                name = f"{line.name}: {e.name}" if threads else e.name
                events.append((name, e.start_ns, e.duration_ns))
    return events, window


def by_name(events, window, match=None):
    """{name: [calls, total ns]} of the events that start inside `window`."""
    out = defaultdict(lambda: [0, 0.0])
    for name, start, dur in events:
        if window and not window[0] <= start < window[1]:
            continue
        if name.endswith(SLICE) or (match and not re.search(match, name)):
            continue
        out[name][0] += 1
        out[name][1] += dur
    return out


def main(argv) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("pb")
    p.add_argument("--match", default=None, help="regex on the event name")
    p.add_argument("--steps", type=int, default=None)
    p.add_argument("--top", type=int, default=25)
    p.add_argument("--threads", action="store_true")
    args = p.parse_args(argv)
    events, window = host_events(args.pb, args.threads)
    steps = args.steps or sum(
        n for name, (n, _) in by_name(events, window).items()
        if name.endswith(STEP_SPANS)) or 1
    rows = sorted(by_name(events, window, args.match).items(),
                  key=lambda kv: -kv[1][1])[:args.top]
    out = {"steps": steps, "calls": {}}
    for name, (n, ns) in rows:
        print(f"{n:8d} calls {n / steps:8.2f} a step {1e-3 * ns / n:9.2f} "
              f"us a call {1e-3 * ns / steps:9.2f} us a step  {name[:100]}")
        out["calls"][name[:100]] = {"calls": n, "per_step": n / steps,
                                    "us_per_step": 1e-3 * ns / steps}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
