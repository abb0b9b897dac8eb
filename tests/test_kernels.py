"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs jnp oracles,
plus full coverage of the tile-variant registry (``kernels.variants``):
every enumerable variant of every kernel must launch and match the
reference, and invalid tiles must be rejected before launch."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref, variants

RNG = np.random.default_rng(42)


def _rand(shape, dtype):
    x = RNG.standard_normal(shape).astype(np.float32)
    return jnp.asarray(x).astype(dtype)


@pytest.mark.parametrize("B,S,K,G,D", [
    (1, 32, 1, 1, 8),
    (2, 64, 2, 4, 16),
    (1, 128, 4, 2, 32),
    (2, 64, 1, 8, 64),      # MQA-style
])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_flash_attention_sweep(B, S, K, G, D, dtype, tol):
    q = _rand((B, S, K, G, D), dtype)
    k = _rand((B, S, K, D), dtype)
    v = _rand((B, S, K, D), dtype)
    out = ops.flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                              interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("window", [8, 32])
def test_flash_attention_local_window(window):
    B, S, K, G, D = 2, 64, 2, 2, 16
    q = _rand((B, S, K, G, D), jnp.float32)
    k = _rand((B, S, K, D), jnp.float32)
    v = _rand((B, S, K, D), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, window=window,
                              block_q=16, block_k=16, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,T,D,block", [
    (1, 32, 8, 8), (2, 128, 24, 32), (3, 64, 16, 64),
])
def test_rglru_scan_sweep(B, T, D, block):
    a = jnp.asarray(RNG.uniform(0.4, 0.999, (B, T, D)).astype(np.float32))
    b = _rand((B, T, D), jnp.float32)
    out = ops.rglru_scan(a, b, block_t=block, interpret=True)
    want = ref.rglru_scan_ref(a, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("B,T,H,hs,block", [
    (1, 32, 1, 8, 8), (2, 64, 3, 8, 16), (1, 128, 2, 16, 32),
])
def test_wkv6_sweep(B, T, H, hs, block):
    r = _rand((B, T, H, hs), jnp.float32)
    k = _rand((B, T, H, hs), jnp.float32)
    v = _rand((B, T, H, hs), jnp.float32)
    w = jnp.asarray(RNG.uniform(0.2, 0.99, (B, T, H, hs)).astype(np.float32))
    u = _rand((H, hs), jnp.float32)
    o, s = ops.wkv6(r, k, v, w, u, block_t=block, interpret=True)
    def fold(t):
        return t.transpose(0, 2, 1, 3).reshape(B * H, T, hs)
    uu = jnp.broadcast_to(u[None], (B, H, hs)).reshape(B * H, hs)
    o_ref, s_ref = ref.wkv6_ref(fold(r), fold(k), fold(v), fold(w), uu)
    o_ref = o_ref.reshape(B, H, T, hs).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(s.reshape(B * H, hs, hs)), np.asarray(s_ref),
        rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("shape", [(8, 32), (4, 16, 48), (128, 64)])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_rmsnorm_sweep(shape, dtype, tol):
    x = _rand(shape, dtype)
    w = _rand(shape[-1:], dtype)
    out = ops.rmsnorm(x, w, interpret=True)
    want = ref.rmsnorm_ref(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_grad_matches_ref():
    """Kernel path is differentiable (interpret mode) and grads match."""
    B, S, K, G, D = 1, 32, 2, 2, 8
    q = _rand((B, S, K, G, D), jnp.float32)
    k = _rand((B, S, K, D), jnp.float32)
    v = _rand((B, S, K, D), jnp.float32)

    def f_kernel(q):
        return ops.flash_attention(q, k, v, causal=True, block_q=16,
                                   block_k=16, interpret=True).sum()

    def f_ref(q):
        return ref.flash_attention_ref(q, k, v, causal=True).sum()

    g1 = jax.grad(f_kernel)(q)
    g2 = jax.grad(f_ref)(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=2e-4, atol=2e-4)


# --- tile-variant registry coverage (ISSUE 6) ------------------------------
#
# One representative operand set per kernel, sized so the declared grids
# yield several distinct variants after clamp+dedup.  EVERY registry
# variant must launch and agree with the reference.

_FLASH_SHAPES = ((1, 128, 1, 2, 8), (1, 128, 1, 8), (1, 128, 1, 8))
_WKV6_SHAPES = ((1, 128, 2, 8),) * 4 + ((2, 8),)
_WKV6_DECODE_SHAPES = ((2, 4, 40, 32, 128), ()) + ((4, 40, 64),) * 4 + (
    (40, 64),)
# L=2, B=4, H=16, G=2 (8 heads to a group), P=64, N=128
_MAMBA2_DECODE_SHAPES = ((2, 4, 16, 64, 128), (), (4, 16), (4, 16, 64),
                         (4, 2, 128), (4, 2, 128))
_RGLRU_SHAPES = ((1, 256, 8),) * 2
_RMSNORM_SHAPES = ((128, 32), (32,))


def _variant_cases():
    cases = []
    for kernel, shapes in (("flash_attention", _FLASH_SHAPES),
                           ("wkv6", _WKV6_SHAPES),
                           ("wkv6_decode", _WKV6_DECODE_SHAPES),
                           ("mamba2_decode", _MAMBA2_DECODE_SHAPES),
                           ("rglru_scan", _RGLRU_SHAPES),
                           ("rmsnorm", _RMSNORM_SHAPES)):
        for v in variants.variants_for(kernel, shapes):
            cases.append(pytest.param(kernel, shapes, v, id=v.label))
    return cases


def test_registry_covers_every_kernel():
    assert set(variants.kernel_names()) == {
        "flash_attention", "wkv6", "wkv6_decode", "mamba2_decode",
        "rglru_scan", "rmsnorm"}
    for kernel, shapes in (("flash_attention", _FLASH_SHAPES),
                           ("wkv6", _WKV6_SHAPES),
                           ("wkv6_decode", _WKV6_DECODE_SHAPES),
                           ("mamba2_decode", _MAMBA2_DECODE_SHAPES),
                           ("rglru_scan", _RGLRU_SHAPES),
                           ("rmsnorm", _RMSNORM_SHAPES)):
        vs = variants.variants_for(kernel, shapes)
        assert len(vs) >= 2, f"{kernel}: want >= 2 distinct variants"
        assert len({v.params for v in vs}) == len(vs)


@pytest.mark.parametrize("kernel,shapes,variant", _variant_cases())
def test_every_registry_variant_matches_ref(kernel, shapes, variant):
    kw = dict(variant.kwargs(), interpret=True)
    if kernel == "flash_attention":
        q = _rand(shapes[0], jnp.float32)
        k = _rand(shapes[1], jnp.float32)
        v = _rand(shapes[2], jnp.float32)
        out = ops.flash_attention(q, k, v, causal=True, **kw)
        want = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
    elif kernel == "wkv6":
        B, T, H, hs = shapes[0]
        r = _rand(shapes[0], jnp.float32)
        k = _rand(shapes[1], jnp.float32)
        v = _rand(shapes[2], jnp.float32)
        w = jnp.asarray(RNG.uniform(0.2, 0.99, shapes[3]).astype(np.float32))
        u = _rand(shapes[4], jnp.float32)
        o, s = ops.wkv6(r, k, v, w, u, **kw)
        def fold(t):
            return t.transpose(0, 2, 1, 3).reshape(B * H, T, hs)
        uu = jnp.broadcast_to(u[None], (B, H, hs)).reshape(B * H, hs)
        o_ref, s_ref = ref.wkv6_ref(fold(r), fold(k), fold(v), fold(w), uu)
        o_ref = o_ref.reshape(B, H, T, hs).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(
            np.asarray(s.reshape(B * H, hs, hs)), np.asarray(s_ref),
            rtol=2e-4, atol=2e-4)
    elif kernel == "wkv6_decode":
        state = _rand(shapes[0], jnp.float32)
        r, k, v = (_rand(sh, jnp.float32) for sh in shapes[2:5])
        w = jnp.asarray(RNG.uniform(0.2, 0.99, shapes[5]).astype(np.float32))
        u = _rand(shapes[6], jnp.float32)
        o, s = ops.wkv6_decode(state, 1, r, k, v, w, u, **kw)
        o_ref, s_ref = ref.wkv6_decode_ref(state, 1, r, k, v, w, u)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                   rtol=1e-5, atol=1e-4)
        np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                                   rtol=1e-6, atol=1e-6)
    elif kernel == "mamba2_decode":
        state = _rand(shapes[0], jnp.float32)
        decay = jnp.asarray(RNG.uniform(0.2, 0.99, shapes[2])
                            .astype(np.float32))
        u, b, c = (_rand(sh, jnp.float32) for sh in shapes[3:6])
        y, s = ops.mamba2_decode(state, 1, decay, u, b, c, **kw)
        y_ref, s_ref = ref.mamba2_decode_ref(state, 1, decay, u, b, c)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-4)
        np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(s[0]), np.asarray(state[0]))
    elif kernel == "rglru_scan":
        a = jnp.asarray(RNG.uniform(0.4, 0.999, shapes[0])
                        .astype(np.float32))
        b = _rand(shapes[1], jnp.float32)
        out = ops.rglru_scan(a, b, **kw)
        want = ref.rglru_scan_ref(a, b)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
    else:
        x = _rand(shapes[0], jnp.float32)
        w = _rand(shapes[1], jnp.float32)
        out = ops.rmsnorm(x, w, **kw)
        want = ref.rmsnorm_ref(x, w)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


class TestVariantValidation:
    def test_invalid_tiles_rejected(self):
        """A non-dividing tile (after clamping) is refused both by
        validate_variant (None) and by kernel_roofline (ValueError) —
        the tuner never enumerates or prices an unlaunchable tile."""
        bad = (((1, 96, 1, 2, 8), (1, 96, 1, 8), (1, 96, 1, 8)),)
        assert variants.validate_variant(
            "flash_attention", bad[0], {"block_q": 64, "block_k": 32}) \
            is None
        with pytest.raises(ValueError):
            variants.kernel_roofline(
                "flash_attention", {"block_q": 64, "block_k": 32}, bad[0])
        assert variants.validate_variant(
            "wkv6", ((1, 96, 2, 8),) * 4 + ((2, 8),), {"block_t": 64}) \
            is None
        assert variants.validate_variant(
            "rglru_scan", ((1, 96, 8),) * 2, {"block_t": 64}) is None

    def test_clamped_variants_dedupe(self):
        """block_q=256 on a 128-token sequence collapses onto block_q=128:
        one launch, one enumerated variant."""
        vs = variants.variants_for("flash_attention", _FLASH_SHAPES)
        assert all(dict(v.params)["block_q"] <= 128 for v in vs)
        assert len(vs) == 4          # {64,128} x {64,128} after dedup

    def test_rmsnorm_canon_mirrors_ops_halving(self):
        """ops.rmsnorm halves block_rows until it divides; the registry's
        canonicalisation must land on the same launched tile."""
        v = variants.validate_variant("rmsnorm", ((96, 32), (32,)),
                                      {"block_rows": 256})
        assert dict(v.params)["block_rows"] == 96 // 32 or \
            96 % dict(v.params)["block_rows"] == 0

    def test_roofline_bytes_vary_across_tiles(self):
        """The whole point of the kernel axis: kernel_s must differ across
        tile candidates.  Flash attention re-reads K/V once per q tile, so
        smaller block_q => more bytes."""
        f64, b64 = variants.kernel_roofline(
            "flash_attention", {"block_q": 64, "block_k": 64},
            _FLASH_SHAPES)
        f128, b128 = variants.kernel_roofline(
            "flash_attention", {"block_q": 128, "block_k": 64},
            _FLASH_SHAPES)
        assert f64 == f128           # same math
        assert b64 > b128            # more K/V traffic with smaller tiles

    def test_bind_variant_identity_stable(self):
        """Bound callables are memoized: backend jit caches key on fn
        identity, so the same (fn, params) must give the SAME object."""
        fn = ops.rmsnorm
        p = (("block_rows", 64),)
        assert variants.bind_variant(fn, p) is variants.bind_variant(fn, p)
        assert variants.bind_variant(fn, p).keywords == {"block_rows": 64}
