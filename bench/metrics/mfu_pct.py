"""Whole step: the operations of every image served ok in the window
(counts.flop_per_image) over the window's length times the chips' bf16
peak."""
import counts


def read(run):
    if run.peak is None:
        return None
    flop = len(run.client["latency_ok_s"]) * counts.flop_per_image(
        run.size["img"], run.size["scale"], run.size["num_classes"])
    return 100.0 * flop / (run.seconds * run.chips * run.peak["bf16_flop_s"])
