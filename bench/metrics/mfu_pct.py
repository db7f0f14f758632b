"""Whole step: the operations of every request served ok in the window
(the model's `flop_per_request`) over the window's length times the
chips' bf16 peak."""


def read(run):
    if run.peak is None:
        return None
    flop = len(run.client["latency_ok_s"]) * run.model.flop_per_request(
        run.size)
    return 100.0 * flop / (run.seconds * run.chips * run.peak["bf16_flop_s"])
