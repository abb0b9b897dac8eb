"""Every registered tile of the four Pallas kernels compiles for a TPU v5e.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached, so these tests need no chip.  Operands are at the
widths of the models that use each kernel: flash_attention at
qwen2.5-14b's layout (40 q heads over 8 kv heads of 128, S = T = 2048,
bf16), wkv6 at rwkv6-3b's (40 heads of 64, T = 2048), rglru_scan at
recurrentgemma-2b's width (2560) and rmsnorm at d = 5120.  The topology
is described inside a fixture, so only the worker that runs this file
loads the TPU library; all of these tests live in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops, variants

_FLASH = ((1, 2048, 8, 5, 128), (1, 2048, 8, 128), (1, 2048, 8, 128))
_WKV6 = ((1, 2048, 40, 64),) * 4 + ((40, 64),)
_RGLRU = ((1, 2048, 2560),) * 2
_RMSNORM = ((2048, 5120), (5120,))

_DTYPES = {
    "flash_attention": (jnp.bfloat16,) * 3,
    "wkv6": (jnp.bfloat16,) * 3 + (jnp.float32, jnp.bfloat16),
    "rglru_scan": (jnp.float32,) * 2,
    "rmsnorm": (jnp.bfloat16,) * 2,
}

_CALLS = {
    "flash_attention": lambda kw: (
        lambda q, k, v: ops.flash_attention(q, k, v, causal=True, **kw)),
    "wkv6": lambda kw: (
        lambda r, k, v, w, u: ops.wkv6(r, k, v, w, u, **kw)),
    "rglru_scan": lambda kw: (lambda a, b: ops.rglru_scan(a, b, **kw)),
    "rmsnorm": lambda kw: (lambda x, w: ops.rmsnorm(x, w, **kw)),
}


def _cases():
    cases = []
    for kernel, shapes in (("flash_attention", _FLASH), ("wkv6", _WKV6),
                           ("rglru_scan", _RGLRU), ("rmsnorm", _RMSNORM)):
        for v in variants.variants_for(kernel, shapes):
            cases.append(pytest.param(kernel, shapes, v, id=v.label))
    return cases


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip cannot be read back without one:
    keep such compiles out of the persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_v5e_is_described(topo):
    assert topo.devices[0].device_kind == "TPU v5 lite"


@pytest.mark.parametrize("kernel,shapes,variant", _cases())
def test_kernel_tile_compiles_for_v5e(kernel, shapes, variant, one_chip,
                                      no_compile_cache):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in zip(shapes, _DTYPES[kernel])]
    fn = _CALLS[kernel](dict(variant.kwargs(), interpret=False))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
