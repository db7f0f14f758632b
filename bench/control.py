"""The control of `correct`, read on the chip at a configuration's size.

    python bench/control.py --config rn50-x15-1chip --seeds 1 2 3

For each seed, the control of the model that the configuration names
(its module's `control_gap`: the reference, in a precision below the
served one, put in the program's place for every copy's every batch
bucket) is judged as a run judges the daemon's outputs. Every reading has
to be far above the model's `GAP_LIMIT`. The benchmark's own runs never
run this; the readings and the limit set from them are in PERF.md.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--config", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    import jax
    from run import load_config, load_model
    cfg = load_config(args.config)
    model = load_model(cfg)
    size = model.size(cfg, False)
    dev = jax.devices()[0]
    print(f"[control] {dev.platform} {dev.device_kind}", file=sys.stderr)
    readings = {}
    for seed in args.seeds:
        t0 = time.monotonic()
        readings[seed] = model.control_gap(seed, cfg["copies"], size)
        print(f"[control] seed {seed}: logit_gap {readings[seed]!r} limit "
              f"{model.GAP_LIMIT!r} ({time.monotonic() - t0:.1f} s)",
              file=sys.stderr, flush=True)
    print(json.dumps({"platform": dev.platform, "kind": dev.device_kind,
                      "config": args.config, "control_gap": readings,
                      "limit": model.GAP_LIMIT}))
    return 0 if min(readings.values()) > model.GAP_LIMIT else 1


if __name__ == "__main__":
    sys.exit(main())
