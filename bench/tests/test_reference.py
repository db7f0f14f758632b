"""The reference rebuilds what the daemon serves, and the comparison that
decides `correct` passes the program's bf16 forward and fails the float8
control, here at the CPU tests' 1/16 width (bench/control.py reads both at
the cells' own size on the chip)."""
import jax
import numpy as np
import pytest

import reference
from run import LOGIT_GAP_LIMIT, REHEARSAL_SIZE

SEEDS = (2**31 + 11, 2**31 + 12, 2**31 + 13)


@pytest.fixture(scope="module")
def served():
    """The program's bf16 outputs for every bucket of one copy per seed."""
    from repro.serving.engine import make_resnet_model
    dev = jax.devices()[0]
    out = {}
    for seed in SEEDS:
        m = make_resnet_model("m0", seed=seed, **REHEARSAL_SIZE)
        m.compile([dev])
        m.load(dev)
        out[seed] = (m, {b: np.asarray(m.execute(b, m._input(b), dev),
                                       np.float32)
                         for b in reference.BUCKETS})
    return out


def test_reference_rebuilds_the_daemons_weights_and_inputs(served):
    for seed, (m, _) in served.items():
        w = reference.weights(seed, REHEARSAL_SIZE["scale"])
        for a, b in zip(jax.tree.leaves(m.host_params), jax.tree.leaves(w)):
            assert np.array_equal(np.asarray(a, np.float32),
                                  np.asarray(b, np.float32))
        xs = reference.inputs(seed, REHEARSAL_SIZE["img"])
        assert all(np.array_equal(m._input(b), xs[b]) for b in xs)


def test_program_passes_and_float8_control_fails(served):
    for seed, (_, outs) in served.items():
        got = reference.compare({(0, b): [o] for b, o in outs.items()},
                                seed, **REHEARSAL_SIZE)
        assert got["rows"] == sum(reference.BUCKETS)
        assert got["logit_gap_max"] < LOGIT_GAP_LIMIT / 2
        control = reference.control_gap(seed, 1, **REHEARSAL_SIZE)
        assert control > 2 * LOGIT_GAP_LIMIT


def test_an_altered_row_fails():
    seed = SEEDS[0]
    w = reference.weights(seed, REHEARSAL_SIZE["scale"])
    x = reference.inputs(seed, REHEARSAL_SIZE["img"])[4]
    ref = np.asarray(reference.forward(w, x))
    bad = ref.copy()
    bad[2] *= 1 + 2 * LOGIT_GAP_LIMIT
    gaps = reference.row_gaps(bad, ref)
    assert gaps[2] > LOGIT_GAP_LIMIT and np.all(np.delete(gaps, 2) == 0)
