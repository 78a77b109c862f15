#!/usr/bin/env python
"""Offline roofline re-check: rebuild each calibration point's model on
the CPU and recompute the ROOFLINE simulated time against the
measured_ms recorded in an existing sim_calibration.json — lets cost-
model constants be tuned without burning a fresh on-chip sweep per
iteration (the final numbers still come from a real re-sweep).

  python benchmarks/retune_roofline.py [path/to/sim_calibration.json]
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dlrm_flexflow_tpu.utils.testing import ensure_cpu_devices  # noqa: E402

ensure_cpu_devices(1)


def main():
    path = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "sim_calibration.json")
    rows = {r["point"]: r for r in json.load(open(path))}

    import calibrate_sim as cal
    from dlrm_flexflow_tpu.search.mcmc import default_strategy
    from dlrm_flexflow_tpu.search.simulator import Simulator

    worst = 0.0
    for name, make in cal.calibration_points():
        if name not in rows:
            continue
        _, model, _ = make()
        strat = default_strategy(model, 1)
        sim_roof = Simulator(model).simulate(strat, 1) * 1e3
        real = rows[name]["measured_ms"]
        err = sim_roof / real - 1.0
        worst = max(worst, abs(err))
        print(f"{name:32s} real {real:8.3f} ms | roofline {sim_roof:8.3f} "
              f"({err:+.0%})")
    print(f"worst roofline |err|: {worst:.0%}")


if __name__ == "__main__":
    from dlrm_flexflow_tpu import use_compile_cache
    use_compile_cache()
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    main()
