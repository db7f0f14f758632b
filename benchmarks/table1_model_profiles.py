"""Table 1: per-model LOAD/INFER profiles.

Two parts: (a) real measured profiles of the CPU-served models (reduced
ResNet + LM decode engines) — the live analogue of the paper's profiling
step; (b) the roofline-derived TPU v5e profiles for the assigned LM
architectures (written by benchmarks/roofline.py from dry-run artifacts).
"""
from __future__ import annotations

import jax

from benchmarks.common import report_line, write_csv
from repro.serving.engine import make_lm_decode_model, make_resnet_model


def run(quick: bool = False):
    rows = []
    specs = [("resnet_tiny", lambda: make_resnet_model(
        "resnet_tiny", scale=16, img=64, batches=(1, 2, 4)))]
    if not quick:
        specs += [
            ("resnet_small", lambda: make_resnet_model(
                "resnet_small", scale=8, img=64, batches=(1, 2, 4))),
            ("qwen2_decode", lambda: make_lm_decode_model(
                "qwen2_decode", "qwen2-0.5b", batches=(1, 2, 4), ctx=128)),
            ("mamba2_decode", lambda: make_lm_decode_model(
                "mamba2_decode", "mamba2-130m", batches=(1, 2, 4), ctx=128)),
        ]
    for name, mk in specs:
        jm = mk()
        prof = jm.seed_profiles(jax.devices()[0])
        load_ms = prof[("LOAD", name, 1)] * 1e3
        b_ms = {b: prof[("INFER", name, b)] * 1e3
                for b in jm.batches}
        rows.append((name, jm.weights_bytes / 1e6, load_ms,
                     *[b_ms.get(b, float("nan")) for b in (1, 2, 4)]))
        report_line(f"table1_{name}", b_ms[1] * 1e3,
                    f"load_ms={load_ms:.2f};b1_ms={b_ms[1]:.2f}")
    write_csv("table1_model_profiles", rows,
              ["model", "weights_mb", "load_ms", "b1_ms", "b2_ms", "b4_ms"])
    return rows
