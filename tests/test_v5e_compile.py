"""The served ResNet-50, at its published widths, compiles for a TPU v5e.

A described (not attached) v5e chip stands in for the real one: the TPU
compiler refuses here what it would refuse on the chip, and its memory
analysis says whether the program fits the chip's 16 GB. Both compiles
stay in this one file, so one test worker loads the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.models import params as pspec
from repro.models.resnet import resnet50_forward, resnet50_spec
from repro.serving.engine import PUBLISHED, Executables

V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def v5e_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs in /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield topo.devices[0]


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip can be written to the persistent
    cache but never read back without one; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("batch", [1, 8, 16])
def test_resnet50_bucket_compiles_for_v5e(v5e_chip, no_persistent_cache,
                                          batch):
    spec = resnet50_spec(num_classes=1000, scale=PUBLISHED["scale"])
    img = PUBLISHED["img"]
    weights = pspec.param_bytes(spec)
    assert weights == 51_112_064            # bf16, what a LOAD moves
    exe = Executables(resnet50_forward).get(
        v5e_chip, batch, pspec.abstract(spec),
        jax.ShapeDtypeStruct((batch, img, img, 3), jnp.float32))
    mem = exe.memory_analysis()
    assert mem.argument_size_in_bytes >= weights + batch * img * img * 3 * 4
    assert mem.output_size_in_bytes >= batch * 1000 * 2   # bf16 logits
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes)
    assert used < V5E_HBM_BYTES, mem
