"""The control of `correct`, read on the chip at a configuration's size.

    python bench/control.py --config rn50-x15-1chip --seeds 1 2 3

For each seed, every copy's every batch bucket is computed by the float8
forward put in the program's place (reference.py, `control=True`) and
judged as a run judges the daemon's outputs. Every reading has to be far
above run.LOGIT_GAP_LIMIT. The benchmark's own runs never run this; the
readings and the limit set from them are in PERF.md.
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
    import reference
    from run import LOGIT_GAP_LIMIT
    cfg = json.loads((BENCH / "configs" / f"{args.config}.json").read_text())
    dev = jax.devices()[0]
    print(f"[control] {dev.platform} {dev.device_kind}", file=sys.stderr)
    readings = {}
    for seed in args.seeds:
        t0 = time.monotonic()
        readings[seed] = reference.control_gap(
            seed, cfg["copies"], scale=cfg["width_divisor"],
            img=cfg["image_size"], num_classes=cfg["num_classes"])
        print(f"[control] seed {seed}: logit_gap {readings[seed]!r} limit "
              f"{LOGIT_GAP_LIMIT!r} ({time.monotonic() - t0:.1f} s)",
              file=sys.stderr, flush=True)
    print(json.dumps({"platform": dev.platform, "kind": dev.device_kind,
                      "config": args.config, "control_gap": readings,
                      "limit": LOGIT_GAP_LIMIT}))
    return 0 if min(readings.values()) > LOGIT_GAP_LIMIT else 1


if __name__ == "__main__":
    sys.exit(main())
