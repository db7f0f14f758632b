"""ResNet-50 v1.5, as the benchmark sees it: the model module of every
configuration that states `"model": "resnet50"`.

The harness calls only this interface (PERF.md, section 3): `BUCKETS`,
`PROGRAM`, `GAP_LIMIT`, `size`, `served`, `worker_args`, `compare`,
`flop_per_request`, `roofline_s` and, from bench/control.py,
`control_gap`. The rest serves them.

**Operations and bytes**, from the shapes: the benchmark's own arithmetic,
independent of the program. The layer table of ResNet-50 v1.5 (He et al.
2016, stride on the 3x3 convolution of each stage's first block), NHWC
inputs, "SAME" padding. A multiply-add is two operations. Batch-norm scale
and bias, ReLU, pooling and the residual additions are left out of the
operation count: they are under 1% of it and run on the vector units, not
against the bf16 matrix peak.

**The plain reference** that decides `correct`: ResNet-50 in float32. It
imports nothing of the program and takes nothing the program made. It
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

The forward pass is ResNet-50 v1.5 with float32 activations, the bf16
weights widened to float32 and every convolution and product at HIGHEST
precision. `control=True` rounds every operand of every convolution and of
the head to float8 (e4m3, one scale per tensor) instead: the step below
the served bfloat16 that a later change might be tempted to take.

JAX is imported on first use, never with the module: the harness's own
process, which runs the program's controller, imports this module for its
sizes and counts and stays off JAX.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import numpy as np

BUCKETS = (1, 2, 4, 8, 16)     # the batch sizes the daemon compiles
PROGRAM = "jit_resnet50_forward"   # the forward's module in the trace
# Widest gap by which a served row of logits may lie from the float32
# reference's, relative to the reference's norm (row_gaps); set from the
# sound runs' readings and the float8 control's, see PERF.md.
GAP_LIMIT = 0.02
PUBLISHED = {"scale": 1, "img": 224, "num_classes": 1000}
REHEARSAL_SIZE = {"scale": 16, "img": 64}   # the worker's --reduced copies
STAGES = (3, 4, 6, 3)          # bottleneck blocks per stage
WIDTHS = (64, 128, 256, 512)   # bottleneck width per stage (output is 4x)
EXPANSION = 4
E4M3_MAX = 448.0
NOT_FINITE = 1e30     # the gap of a row that holds NaN or infinity


def size(config: dict, rehearsal: bool) -> dict:
    """The sizes the served path runs for `config`: `scale` (width
    divisor), `img` (image side) and `num_classes`; in a rehearsal the
    worker's --reduced copies."""
    out = dict(REHEARSAL_SIZE) if rehearsal else {
        "scale": config["width_divisor"], "img": config["image_size"]}
    out["num_classes"] = config["num_classes"]
    return out


def served(size: dict) -> dict:
    """What the daemon serves at `size`, which the configuration has to
    state as it is."""
    return {"dtype": "bfloat16", "batch_buckets": list(BUCKETS),
            "weights_bytes_per_copy":
                2 * param_count(size["scale"], size["num_classes"])}


def worker_args(size: dict, rehearsal: bool) -> list:
    """Arguments of `repro.runtime.worker` that make it serve `size`: the
    worker serves the published widths, or the rehearsal's."""
    return ["--reduced"] if rehearsal else []


# -- operations and bytes ---------------------------------------------------

def widths(scale: int = 1):
    """Stage widths; `scale` > 1 is the 1/scale-width model of CPU tests."""
    return tuple(max(8, w // scale) for w in WIDTHS)


def conv_layers(img: int = 224, scale: int = 1):
    """(name, out_h, out_w, k, cin, cout) for every convolution."""
    ws = widths(scale)
    h = math.ceil(img / 2)                     # 7x7 stem, stride 2
    yield ("stem", h, h, 7, 3, ws[0])
    h = math.ceil(h / 2)                       # 3x3 max pool, stride 2
    cin = ws[0]
    for si, (n, w) in enumerate(zip(STAGES, ws)):
        for bi in range(n):
            stride = 2 if (bi == 0 and si > 0) else 1
            h_out = math.ceil(h / stride)
            cout = w * EXPANSION
            tag = f"stage{si}.{bi}"
            yield (f"{tag}.conv1", h, h, 1, cin, w)
            yield (f"{tag}.conv2", h_out, h_out, 3, w, w)
            yield (f"{tag}.conv3", h_out, h_out, 1, w, cout)
            if stride != 1 or cin != cout:
                yield (f"{tag}.proj", h_out, h_out, 1, cin, cout)
            cin, h = cout, h_out


def flop_per_image(img: int = 224, scale: int = 1,
                   num_classes: int = 1000) -> int:
    """Operations of one image's forward pass: convolutions and the head."""
    macs = sum(oh * ow * k * k * cin * cout
               for _, oh, ow, k, cin, cout in conv_layers(img, scale))
    head_in = widths(scale)[-1] * EXPANSION
    return 2 * (macs + head_in * num_classes)


def flop_per_request(size: dict) -> int:
    """Operations of one request, one image, at `size`."""
    return flop_per_image(size["img"], size["scale"], size["num_classes"])


def param_count(scale: int = 1, num_classes: int = 1000) -> int:
    """Weights, with each batch norm's scale and bias."""
    n = 0
    for _, _, _, k, cin, cout in conv_layers(224, scale):
        n += k * k * cin * cout + 2 * cout
    return n + widths(scale)[-1] * EXPANSION * num_classes


def bytes_per_exec(batch: int, img: int = 224, scale: int = 1,
                   num_classes: int = 1000, weight_bytes: int = 2,
                   input_bytes: int = 4, logit_bytes: int = 2) -> int:
    """The least HBM traffic of one batch: every weight read once (bf16),
    the float32 input read and the bf16 logits written. Activations that
    spill to HBM would add to it, so this bounds the time from below."""
    return (param_count(scale, num_classes) * weight_bytes
            + batch * img * img * 3 * input_bytes
            + batch * num_classes * logit_bytes)


def roofline_s(batch: int, peak: dict, size: dict = PUBLISHED) -> float:
    """Least time one forward of `batch` images at `size` can take on a
    chip with `peak` (see peaks.py): the larger of its compute and its
    memory time."""
    img, scale, classes = size["img"], size["scale"], size["num_classes"]
    return max(batch * flop_per_image(img, scale, classes)
               / peak["bf16_flop_s"],
               bytes_per_exec(batch, img, scale, classes)
               / peak["hbm_bytes_s"])


# -- the plain reference ----------------------------------------------------

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


@functools.cache
def _jit(fn, **static):
    import jax
    return jax.jit(fn, **static)


def _weights(key, scale: int, num_classes: int):
    import jax
    import jax.numpy as jnp
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
    import jax
    return _jit(_weights, static_argnums=(1, 2))(
        jax.random.PRNGKey(copy_seed), scale, num_classes)


def inputs(copy_seed: int, img: int = 224) -> dict:
    """One copy's input per batch bucket (float32, NHWC)."""
    rng = np.random.default_rng(copy_seed)
    return {b: rng.standard_normal((b, img, img, 3), dtype=np.float32)
            for b in BUCKETS}


def _fp8(a):
    """`a` rounded to float8 e4m3 with one scale for the whole tensor."""
    import jax.numpy as jnp
    s = jnp.max(jnp.abs(a)) / E4M3_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _forward(params, x, control: bool = False):
    import jax
    import jax.numpy as jnp
    highest = jax.lax.Precision.HIGHEST
    q = _fp8 if control else (lambda a: a)
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)

    def conv(y, w, stride=1):
        return jax.lax.conv_general_dilated(
            q(y), q(w), (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=highest)

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
    return jnp.dot(q(y), q(p["head"]), precision=highest)


def forward(params, x, control: bool = False):
    """Logits (B, classes), float32, of ResNet-50 v1.5 on `x` (B, H, W, 3)."""
    return _jit(_forward, static_argnames=("control",))(
        params, x, control=control)


def row_gaps(served: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per row (one image's logits): the L2 distance between the served
    and the reference logits over the reference's L2 norm."""
    served = np.asarray(served, np.float64)
    ref = np.asarray(ref, np.float64)
    gap = np.linalg.norm(served - ref, axis=-1) / np.linalg.norm(ref, axis=-1)
    return np.where(np.isfinite(gap), gap, NOT_FINITE)


def compare(outputs: dict, seed: int, size: dict = PUBLISHED) -> dict:
    """Judge served outputs. `outputs` maps (copy index, bucket) to a list
    of (B, classes) arrays that EXECs of that copy's bucket returned; each
    row is compared with the reference's logits for the same image. Runs
    copy by copy, so only one copy's reference weights exist at a time."""
    worst, rows, arrays = 0.0, 0, 0
    by_copy: dict = {}
    for (i, b), outs in outputs.items():
        by_copy.setdefault(i, {})[b] = outs
    for i in sorted(by_copy):
        w = weights(seed + i, size["scale"], size["num_classes"])
        xs = inputs(seed + i, size["img"])
        for b, outs in sorted(by_copy[i].items()):
            ref = np.asarray(forward(w, xs[b]))
            for out in outs:
                gaps = row_gaps(out, ref)
                worst = max(worst, float(gaps.max()))
                rows += len(gaps)
                arrays += 1
        del w
    return {"logit_gap_max": worst, "rows": rows, "outputs": arrays}


def control_gap(seed: int, n_copies: int, size: dict = PUBLISHED) -> float:
    """The control's reading: the float8 forward put in the program's
    place for every bucket of `n_copies` copies, judged as `compare`
    judges served outputs."""
    worst = 0.0
    for i in range(n_copies):
        w = weights(seed + i, size["scale"], size["num_classes"])
        for b, x in inputs(seed + i, size["img"]).items():
            ref = np.asarray(forward(w, x))
            low = np.asarray(forward(w, x, control=True))
            worst = max(worst, float(row_gaps(low, ref).max()))
    return worst
