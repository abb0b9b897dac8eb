"""Every registered tile of the six Pallas kernels compiles for a TPU v5e.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached, so these tests need no chip.  Operands are at the
widths of the models that use each kernel: flash_attention at
qwen2.5-14b's layout (40 q heads over 8 kv heads of 128, S = T = 2048,
bf16), wkv6 at rwkv6-3b's (40 heads of 64, T = 2048), wkv6_decode at
rwkv6-3b's pool (32 layers, 128 rows, 40 heads of 64), mamba2_decode at
nemotron3-nano-30b-a3b's pool (23 blocks, 64 rows, 64 heads of 64 × 128,
8 groups), rglru_scan at
recurrentgemma-2b's width (2560) and rmsnorm at d = 5120.  The decode
steps of rwkv6-3b (capacity 128) and of nemotron3-nano-30b-a3b (capacity
64, 8 experts held, 1536 positions) are compiled whole, to check that
they update their pools in place, and so is the pool insert of each, to
check that admitting a request writes one row.  The topology is described
inside a fixture, so only the worker that runs this file loads the TPU
library; all of these tests live in this one file.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops, variants
from repro.models import Transformer
from repro.serve.kvpool import (_insert_fn, cache_bytes_per_slot,
                                infer_batch_axes)

_FLASH = ((1, 2048, 8, 5, 128), (1, 2048, 8, 128), (1, 2048, 8, 128))
_WKV6 = ((1, 2048, 40, 64),) * 4 + ((40, 64),)
_WKV6_DECODE = ((32, 128, 40, 32, 128), ()) + ((128, 40, 64),) * 4 + (
    (40, 64),)
_MAMBA2_DECODE = ((23, 64, 64, 64, 128), (), (64, 64), (64, 64, 64),
                  (64, 8, 128), (64, 8, 128))
_RGLRU = ((1, 2048, 2560),) * 2
_RMSNORM = ((2048, 5120), (5120,))

_DTYPES = {
    "flash_attention": (jnp.bfloat16,) * 3,
    "wkv6": (jnp.bfloat16,) * 3 + (jnp.float32, jnp.bfloat16),
    "wkv6_decode": (jnp.float32, jnp.int32) + (jnp.bfloat16,) * 3 + (
        jnp.float32, jnp.bfloat16),
    "mamba2_decode": (jnp.float32, jnp.int32) + (jnp.float32,) * 4,
    "rglru_scan": (jnp.float32,) * 2,
    "rmsnorm": (jnp.bfloat16,) * 2,
}

_CALLS = {
    "flash_attention": lambda kw: (
        lambda q, k, v: ops.flash_attention(q, k, v, causal=True, **kw)),
    "wkv6": lambda kw: (
        lambda r, k, v, w, u: ops.wkv6(r, k, v, w, u, **kw)),
    "wkv6_decode": lambda kw: (
        lambda s, l, r, k, v, w, u: ops.wkv6_decode(s, l, r, k, v, w, u,
                                                    **kw)),
    "mamba2_decode": lambda kw: (
        lambda s, l, d, u, b, c: ops.mamba2_decode(s, l, d, u, b, c, **kw)),
    "rglru_scan": lambda kw: (lambda a, b: ops.rglru_scan(a, b, **kw)),
    "rmsnorm": lambda kw: (lambda x, w: ops.rmsnorm(x, w, **kw)),
}


def _cases():
    cases = []
    for kernel, shapes in (("flash_attention", _FLASH), ("wkv6", _WKV6),
                           ("wkv6_decode", _WKV6_DECODE),
                           ("mamba2_decode", _MAMBA2_DECODE),
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


_POOL = "f32[32,128,40,32,128]"     # rwkv6-3b's state pool at capacity 128
_POOL_LAYER_BYTES = 128 * 40 * 64 * 64 * 4


def _on(sharding):
    def on_chip(t):
        return jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=sharding)
    return on_chip


def _compile_insert(m, params, cache, max_seq, one_chip):
    """The pool insert (``kvpool._insert_fn``) of ``cache`` against the
    cache that a batch-1 prefill of 16 tokens hands back."""
    tokens = jax.ShapeDtypeStruct((1, 16), jnp.int32, sharding=one_chip)
    row = jax.tree.map(_on(one_chip), jax.eval_shape(
        lambda p, t: m.prefill(p, {"tokens": t}, max_seq=max_seq)[1],
        params, tokens))
    axes = tuple(infer_batch_axes(m, max_seq))
    idx = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    return axes, _insert_fn(axes).lower(
        tuple(jax.tree.leaves(cache)), tuple(jax.tree.leaves(row)), idx,
        idx).compile()


def test_rwkv6_decode_updates_state_pool_in_place(one_chip,
                                                  no_compile_cache):
    """The decode step of rwkv6-3b at its published widths, capacity 128,
    with the cache donated: each layer's rows of the pool are updated in
    place, so the program needs no second pool (its temporaries stay under
    one layer's state) and copies no whole pool.  It takes the pool in the
    layout the insert hands it back in, so nothing relays the pool out
    between the two programs."""
    m = Transformer(get_config("rwkv6-3b"))
    C = 128
    params = jax.tree.map(_on(one_chip),
                          jax.eval_shape(m.init, jax.random.key(0)))
    cache = jax.tree.map(_on(one_chip),
                         jax.eval_shape(lambda: m.init_cache(C, 1)))
    assert cache["state"].shape == (32, C, 40, 32, 128)
    tok = {"tokens": jax.ShapeDtypeStruct((C,), jnp.int32, sharding=one_chip)}
    pos = jax.ShapeDtypeStruct((C,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(m.decode_step, donate_argnums=(1,)).lower(
        params, cache, tok, pos).compile()

    assert compiled.memory_analysis().temp_size_in_bytes < _POOL_LAYER_BYTES
    pool_copies = []
    for line in compiled.as_text().splitlines():
        if " = " not in line:
            continue
        rhs = line.split(" = ", 1)[1]
        op = re.search(r"\s(copy(?:-start)?)\(", rhs)
        if op and _POOL in rhs[:op.start()]:
            pool_copies.append(line.strip())
    assert not pool_copies, pool_copies

    _, insert = _compile_insert(m, params, cache, 1, one_chip)
    takes = [f.layout for f in jax.tree.leaves(compiled.input_formats[0][1])]
    hands = [f.layout for f in jax.tree.leaves(insert.output_formats)]
    assert takes == hands


def _batch_major(layout, ax):
    """The batch axis is more major than the lane (minor-most) axis, so a
    slot owns whole 128-lane rows of the pool's tiles, not one lane of
    every tile."""
    return layout.major_to_minor[-1] != ax


def _hlo_shape(t, batch_ax=None):
    dims = list(t.shape)
    if batch_ax is not None:
        dims[batch_ax] = 1
    name = {"float32": "f32", "bfloat16": "bf16"}[jnp.dtype(t.dtype).name]
    return f"{name}[{','.join(map(str, dims))}]"


@pytest.mark.parametrize("arch,capacity,max_seq", [
    ("rwkv6-3b", 128, 1), ("nemotron3-nano-30b-a3b", 64, 1536)])
def test_pool_insert_writes_one_row(arch, capacity, max_seq, one_chip,
                                    no_compile_cache):
    """Admitting a request writes its slot and nothing more.  Compiled for
    a v5e at the benchmark cells' capacities, the insert takes every pooled
    leaf batch-major, needs under two slots of temporaries, and copies
    neither a whole pool nor a row padded out along the batch axis (a
    batch-1 row laid out with its batch axis in the lanes)."""
    cfg = get_config(arch)
    if arch.startswith("nemotron"):
        cfg = dataclasses.replace(cfg, experts_held=8)
    m = Transformer(cfg)
    params = jax.tree.map(_on(one_chip),
                          jax.eval_shape(m.init, jax.random.key(0)))
    cache = jax.tree.map(_on(one_chip), jax.eval_shape(
        lambda: m.init_cache(capacity, max_seq)))
    axes, insert = _compile_insert(m, params, cache, max_seq, one_chip)

    leaves = jax.tree.leaves(cache)
    formats = jax.tree.leaves(insert.input_formats[0][0])
    assert len(formats) == len(leaves) == len(axes)
    for leaf, fmt, ax in zip(leaves, formats, axes):
        assert _batch_major(fmt.layout, ax), (leaf.shape, fmt.layout)

    slot = cache_bytes_per_slot(m, max_seq)
    assert insert.memory_analysis().temp_size_in_bytes < 2 * slot

    pools = {_hlo_shape(t) for t in leaves}
    rows = {_hlo_shape(t, ax): ax for t, ax in zip(leaves, axes)}
    bad = []
    for line in insert.as_text().splitlines():
        hit = re.search(r"= (\w+\[[\d,]*\])\{(\d+)[^}]*\} copy(-start)?\(",
                        line)
        if hit and (hit[1] in pools or (
                hit[1] in rows and int(hit[2]) == rows[hit[1]])):
            bad.append(line.strip()[:160])
    assert not bad, bad


def _materialized_copies(hlo_text, shapes):
    """Copies whose result lands in memory (in the entry computation, or
    as the root of a fusion) with one of ``shapes``."""
    out, entry = [], False
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY"):
            entry = True
        elif not line.strip():
            entry = False
        if " = " not in line:
            continue
        rhs = line.split(" = ", 1)[1]
        op = re.search(r"\s(copy(?:-start)?)\(", rhs)
        if op and (entry or line.lstrip().startswith("ROOT")) and any(
                s in rhs[:op.start()] for s in shapes):
            out.append(line.strip()[:160])
    return out


def _readers(hlo_text, shape):
    """(name, opcode, line) of each instruction of the entry computation
    that takes a value of ``shape``, or a tuple holding one, as an
    operand."""
    held, out, entry = set(), [], False
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY"):
            entry = True
            continue
        if entry and line.strip() == "}":
            break
        if not entry or " = " not in line:
            continue
        name, rhs = line.strip().split(" = ", 1)
        name = name.replace("ROOT ", "").lstrip("%")
        op = re.search(r"\s([a-z][\w\-]*)\(", rhs)
        if set(re.findall(r"%([\w.\-]+)", rhs[op.end():])) & held:
            out.append((name, op[1], line.strip()))
        if shape + "{" in rhs[:op.start()]:
            held.add(name)
    return out


def test_nemotron_h_decode_fits_and_updates_pool_in_place(one_chip,
                                                         no_compile_cache):
    """The decode step of nemotron3-nano-30b-a3b at its published widths,
    holding 8 of the 128 experts, at the benchmark cell's capacity 64 and
    1536 positions, cache donated: the weights (8.08 GB) and the pool fit
    the chip's 16 GB, the program needs no second pool (temporaries under
    one block's SSM state), and no state- or KV-shaped copy is
    materialized, of the whole pool or of one block's rows.  The SSM pool
    is read only by the 23 ``mamba2_decode`` kernels, each under the
    ``mamba2`` scope: no fusion slices a block's state out of it, so each
    block's state is read once and written once."""
    cfg = dataclasses.replace(get_config("nemotron3-nano-30b-a3b"),
                              experts_held=8)
    m = Transformer(cfg)
    C, T = 64, 1536

    def on_chip(t):
        return jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=one_chip)

    params = jax.tree.map(on_chip, jax.eval_shape(m.init, jax.random.key(0)))
    cache = jax.tree.map(on_chip, jax.eval_shape(lambda: m.init_cache(C, T)))
    weight_bytes = sum(t.size * t.dtype.itemsize
                       for t in jax.tree.leaves(params))
    assert 8.0e9 < weight_bytes < 8.1e9
    assert cache["ssm"].shape == (23, C, 64, 64, 128)
    assert cache["kv"]["k"].shape == (6, C, T, 2, 128)
    tok = {"tokens": jax.ShapeDtypeStruct((C,), jnp.int32, sharding=one_chip)}
    pos = jax.ShapeDtypeStruct((C,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(m.decode_step, donate_argnums=(1,)).lower(
        params, cache, tok, pos).compile()

    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
    assert mem.temp_size_in_bytes < C * 64 * 64 * 128 * 4
    text = compiled.as_text()
    for pool in ("f32[23,64,64,64,128]", "bf16[6,64,1536,2,128]",
                 "bf16[23,64,3,6144]"):
        assert not [ln for ln in text.splitlines()
                    if pool + "{" in ln and re.search(r"\scopy(-start)?\(",
                                                      ln)], pool
    rows = ("f32[64,64,64,128]", "f32[1,64,64,64,128]",
            "bf16[64,1536,2,128]", "bf16[1,64,1536,2,128]")
    assert not _materialized_copies(text, rows)

    readers = _readers(text, "f32[23,64,64,64,128]")
    kernels = [ln for name, op, ln in readers if op == "custom-call"
               and re.fullmatch(r"mamba2_decode(\.\d+)?", name)]
    assert len(kernels) == 23
    assert all(re.search(r'op_name="[^"]*/mamba2/', ln) for ln in kernels)
    others = [ln[:160] for name, op, ln in readers
              if op not in ("custom-call", "get-tuple-element", "tuple")]
    assert not others, others
    assert len(kernels) == sum(op == "custom-call" for _, op, _ in readers)
