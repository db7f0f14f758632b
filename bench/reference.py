"""The plain reference that decides `correct`: ResNet-50 in float32.

It imports nothing of the program and takes nothing the program made. It
rebuilds a served copy's weights and inputs from the seed the benchmark
gave the daemon, by the recipe the daemon documents for its seeded
copies: copy `m{i}` has seed `seed + i`; its weights are one
`jax.random.split` of `PRNGKey(seed + i)` over the parameter leaves in
pytree order, each convolution and the head drawn from a normal truncated
to two standard deviations with standard deviation 1/sqrt(fan_in) (fan-in
is the second-to-last dimension), batch-norm scales 1 and biases 0,
rounded to bfloat16 as they are served; the input of batch bucket `b` is
the next `b` images of `numpy.random.default_rng(seed + i)`, drawn for the
buckets in increasing order, float32 standard normals.

The forward pass is ResNet-50 v1.5 (stride on each stage's first 3x3
convolution, "SAME" padding, NHWC) with float32 activations, the bf16
weights widened to float32 and every convolution and product at HIGHEST
precision. `control=True` rounds every operand of every convolution and of
the head to float8 (e4m3, one scale per tensor) instead: the step below
the served bfloat16 that a later change might be tempted to take.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from counts import BUCKETS, EXPANSION, STAGES, widths

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


@dataclasses.dataclass(frozen=True)
class Leaf:
    shape: tuple
    init: str          # "normal" | "ones" | "zeros"


def _conv(cin, cout, k):
    return Leaf((k, k, cin, cout), "normal")


def _bn(c):
    return {"scale": Leaf((c,), "ones"), "bias": Leaf((c,), "zeros")}


def param_tree(scale: int = 1, num_classes: int = 1000) -> dict:
    """The parameter leaves of ResNet-50, named as the daemon names them
    (the names fix the pytree order, and so which key draws which leaf)."""
    ws = widths(scale)
    tree = {"stem": _conv(3, ws[0], 7), "bn_stem": _bn(ws[0])}
    cin = ws[0]
    for si, (n, w) in enumerate(zip(STAGES, ws)):
        blocks = []
        for bi in range(n):
            stride = 2 if (bi == 0 and si > 0) else 1
            cout = w * EXPANSION
            blk = {"conv1": _conv(cin, w, 1), "bn1": _bn(w),
                   "conv2": _conv(w, w, 3), "bn2": _bn(w),
                   "conv3": _conv(w, cout, 1), "bn3": _bn(cout)}
            if stride != 1 or cin != cout:
                blk["proj"] = _conv(cin, cout, 1)
                blk["bn_proj"] = _bn(cout)
            blocks.append(blk)
            cin = cout
        tree[f"stage{si}"] = tuple(blocks)
    tree["head"] = Leaf((cin, num_classes), "normal")
    return tree


@functools.partial(jax.jit, static_argnums=(1, 2))
def _weights(key, scale: int, num_classes: int):
    leaves, treedef = jax.tree.flatten(param_tree(scale, num_classes))
    keys = jax.random.split(key, len(leaves))
    out = []
    for leaf, k in zip(leaves, keys):
        if leaf.init == "ones":
            a = jnp.ones(leaf.shape, jnp.bfloat16)
        elif leaf.init == "zeros":
            a = jnp.zeros(leaf.shape, jnp.bfloat16)
        else:
            std = 1.0 / np.sqrt(max(leaf.shape[-2], 1))
            a = (jax.random.truncated_normal(k, -2.0, 2.0, leaf.shape,
                                             jnp.float32) * std
                 ).astype(jnp.bfloat16)
        out.append(a)
    return jax.tree.unflatten(treedef, out)


def weights(copy_seed: int, scale: int = 1, num_classes: int = 1000):
    """One copy's bf16 weights, made on the default device in one call."""
    return _weights(jax.random.PRNGKey(copy_seed), scale, num_classes)


def inputs(copy_seed: int, img: int = 224) -> dict:
    """One copy's input per batch bucket (float32, NHWC)."""
    rng = np.random.default_rng(copy_seed)
    return {b: rng.standard_normal((b, img, img, 3), dtype=np.float32)
            for b in BUCKETS}


def _fp8(a):
    """`a` rounded to float8 e4m3 with one scale for the whole tensor."""
    s = jnp.max(jnp.abs(a)) / E4M3_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@functools.partial(jax.jit, static_argnames=("control",))
def forward(params, x, control: bool = False):
    """Logits (B, classes), float32, of ResNet-50 v1.5 on `x` (B, H, W, 3)."""
    q = _fp8 if control else (lambda a: a)
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)

    def conv(y, w, stride=1):
        return jax.lax.conv_general_dilated(
            q(y), q(w), (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)

    def bn(b, y):
        return y * b["scale"] + b["bias"]

    y = jax.nn.relu(bn(p["bn_stem"], conv(x.astype(jnp.float32),
                                           p["stem"], 2)))
    y = jax.lax.reduce_window(y, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
    for si in range(len(STAGES)):
        for bi, blk in enumerate(p[f"stage{si}"]):
            stride = 2 if (bi == 0 and si > 0) else 1
            r = y
            h = jax.nn.relu(bn(blk["bn1"], conv(y, blk["conv1"])))
            h = jax.nn.relu(bn(blk["bn2"], conv(h, blk["conv2"], stride)))
            h = bn(blk["bn3"], conv(h, blk["conv3"]))
            if "proj" in blk:
                r = bn(blk["bn_proj"], conv(y, blk["proj"], stride))
            y = jax.nn.relu(h + r)
    y = y.mean(axis=(1, 2))
    return jnp.dot(q(y), q(p["head"]), precision=HIGHEST)


NOT_FINITE = 1e30     # the gap of a row that holds NaN or infinity


def row_gaps(served: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per row (one image's logits): the L2 distance between the served
    and the reference logits over the reference's L2 norm."""
    served = np.asarray(served, np.float64)
    ref = np.asarray(ref, np.float64)
    gap = np.linalg.norm(served - ref, axis=-1) / np.linalg.norm(ref, axis=-1)
    return np.where(np.isfinite(gap), gap, NOT_FINITE)


def compare(outputs: dict, seed: int, *, scale: int = 1, img: int = 224,
            num_classes: int = 1000) -> dict:
    """Judge served outputs. `outputs` maps (copy index, bucket) to a list
    of (B, classes) arrays that EXECs of that copy's bucket returned; each
    row is compared with the reference's logits for the same image. Runs
    copy by copy, so only one copy's reference weights exist at a time."""
    worst, rows, arrays = 0.0, 0, 0
    by_copy: dict = {}
    for (i, b), outs in outputs.items():
        by_copy.setdefault(i, {})[b] = outs
    for i in sorted(by_copy):
        w = weights(seed + i, scale, num_classes)
        xs = inputs(seed + i, img)
        for b, outs in sorted(by_copy[i].items()):
            ref = np.asarray(forward(w, xs[b]))
            for out in outs:
                gaps = row_gaps(out, ref)
                worst = max(worst, float(gaps.max()))
                rows += len(gaps)
                arrays += 1
        del w
    return {"logit_gap_max": worst, "rows": rows, "outputs": arrays}


def control_gap(seed: int, n_copies: int, *, scale: int = 1, img: int = 224,
                num_classes: int = 1000) -> float:
    """The control's reading: the float8 forward put in the program's
    place for every bucket of `n_copies` copies, judged as `compare`
    judges served outputs."""
    worst = 0.0
    for i in range(n_copies):
        w = weights(seed + i, scale, num_classes)
        for b, x in inputs(seed + i, img).items():
            ref = np.asarray(forward(w, x))
            low = np.asarray(forward(w, x, control=True))
            worst = max(worst, float(row_gaps(low, ref).max()))
    return worst
