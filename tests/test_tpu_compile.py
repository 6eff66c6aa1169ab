"""The Pallas kernels of the main path compile for a TPU v5e at real widths.

Nothing runs: each kernel is lowered from shapes and compiled for a
described (not attached) ``v5e:2x2`` topology, which refuses what interpret
mode accepts — blocks that break the (8, 128) tiling rule, more VMEM than a
kernel may use, programs that overflow HBM.  The topology is described
inside a fixture (never at import), so every test worker collects the same
tests and only the worker that runs this file loads the TPU compiler.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels.flashattn.kernel import flash_attention_pallas
from repro.kernels.krum.kernel import pairwise_sq_dists_pallas
from repro.kernels.phocas.kernel import phocas_counts_pallas, phocas_pallas
from repro.kernels.trmean.kernel import trmean_counts_pallas, trmean_pallas

GRANITE = get_arch("granite-8b")
# One granite-8b MLP matrix, flattened: the d of one layer-gradient leaf.
MLP_D = GRANITE.d_model * GRANITE.d_ff


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent compile cache
    off: entries written for a described chip cannot be read back here."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            try:
                topo = topologies.get_topology_desc(platform="tpu",
                                                    topology_name="v5e:2x2")
            except Exception as e:
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _compiled_text(fn, *args, **kw) -> str:
    return fn.lower(*args, interpret=False, **kw).compile().as_text()


@pytest.mark.parametrize("m", [4, 20])
@pytest.mark.parametrize("kernel", [phocas_pallas, phocas_counts_pallas,
                                    trmean_pallas, trmean_counts_pallas],
                         ids=lambda f: f.__name__)
def test_trim_kernels_compile(one_chip, kernel, m):
    u = jax.ShapeDtypeStruct((m, MLP_D), jnp.float32, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(kernel, u, 1)


# The benchmark cells' shapes: the train cell's (m=4, 268,447,744-parameter)
# worker matrix and the serve cell's k=3 replicas' (3, 786,432) logits.
CELL_SHAPES = [(4, 268_447_744), (3, 786_432)]


@pytest.mark.parametrize("shape", CELL_SHAPES, ids=lambda s: "x".join(
    map(str, s)))
@pytest.mark.parametrize("kernel", [phocas_pallas, phocas_counts_pallas],
                         ids=lambda f: f.__name__)
def test_phocas_kernels_read_the_matrix_in_place(one_chip, kernel, shape):
    """At the cells' shapes the kernel is one custom call named as the
    trace reads it (``bench/metrics/agg_kernel_ms.py``), fed straight from
    the parameter: no pad, and no float32 (m, d) copy ahead of it."""
    m, d = shape
    u = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    text = _compiled_text(kernel, u, 1)
    name = kernel.__name__
    call = re.search(rf"^\s*%{name}(\.\d+)? = .* custom-call\(%u[.\d]*\)"
                     r'.*custom_call_target="tpu_custom_call"', text, re.M)
    assert call, f"no tpu_custom_call named {name} on the parameter"
    assert not re.search(r"\bpad\(", text)
    assert not re.search(rf"= f32\[{m},{d}\]\S* copy\(", text)


def test_krum_gram_kernel_compiles(one_chip):
    u = jax.ShapeDtypeStruct((20, MLP_D), jnp.float32, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(pairwise_sq_dists_pallas, u)


def test_flash_attention_compiles(one_chip):
    hd, S = GRANITE.head_dim, 2048
    q = jax.ShapeDtypeStruct((1, S, GRANITE.num_heads, hd), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, S, GRANITE.num_kv_heads, hd), jnp.bfloat16,
                              sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(flash_attention_pallas, q, kv,
                                               kv)
