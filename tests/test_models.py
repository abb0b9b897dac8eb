"""Per-arch smoke tests (reduced configs) + cache-consistency checks."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ALL_ARCHS, get_config, reduced
from repro.models import Transformer

RNG = np.random.default_rng(7)


def _batch(cfg, B=2, S=32):
    out = {}
    if cfg.input_embeds:
        out["embeds"] = jnp.asarray(RNG.standard_normal(
            (B, S, cfg.d_model)).astype(np.float32))
    else:
        out["tokens"] = jnp.asarray(
            RNG.integers(0, cfg.vocab, (B, S)), jnp.int32)
    lshape = (B, S, cfg.n_codebooks) if cfg.n_codebooks else (B, S)
    out["labels"] = jnp.asarray(RNG.integers(0, cfg.vocab, lshape),
                                jnp.int32)
    return out


@pytest.mark.parametrize("full_cfg", ALL_ARCHS, ids=lambda c: c.name)
def test_arch_smoke_forward(full_cfg):
    """Reduced same-family config: one forward pass, finite loss, correct
    output shapes (the FULL config is exercised by the dry-run)."""
    cfg = reduced(full_cfg)
    m = Transformer(cfg)
    params = m.init(jax.random.key(0))
    batch = _batch(cfg)
    loss, metrics = jax.jit(m.loss)(params, batch)
    assert np.isfinite(float(loss)), full_cfg.name
    assert float(loss) > 0


@pytest.mark.parametrize("name", ["internlm2-20b", "recurrentgemma-2b",
                                  "rwkv6-3b"])
def test_arch_train_step(name):
    cfg = reduced(get_config(name))
    m = Transformer(cfg)
    params = m.init(jax.random.key(0))
    batch = _batch(cfg)
    grads, _ = jax.grad(m.loss, has_aux=True)(params, batch)
    sq = sum(float(jnp.sum(jnp.square(g))) for g in jax.tree.leaves(grads))
    assert np.isfinite(sq) and sq > 0


@pytest.mark.parametrize("name", ["internlm2-20b", "qwen2.5-14b",
                                  "recurrentgemma-2b", "rwkv6-3b",
                                  "musicgen-large", "chameleon-34b"])
def test_decode_matches_prefill(name):
    """decode_step after prefill(S) == last logits of prefill(S+1)."""
    cfg = reduced(get_config(name))
    m = Transformer(cfg)
    params = m.init(jax.random.key(1))
    B, S = 2, 24
    toks = RNG.integers(0, cfg.vocab, (B, S + 1))
    if cfg.input_embeds:
        emb = RNG.standard_normal((B, S + 1, cfg.d_model)).astype(
            np.float32)
        b_s = {"embeds": jnp.asarray(emb[:, :S])}
        b_s1 = {"embeds": jnp.asarray(emb)}
        nxt = {"embeds": jnp.asarray(emb[:, S])}
    else:
        b_s = {"tokens": jnp.asarray(toks[:, :S], jnp.int32)}
        b_s1 = {"tokens": jnp.asarray(toks, jnp.int32)}
        nxt = {"tokens": jnp.asarray(toks[:, S], jnp.int32)}
    _, cache = m.prefill(params, b_s, max_seq=S + 8)
    ld, _ = m.decode_step(params, cache, nxt, jnp.full((B,), S, jnp.int32))
    lf, _ = m.prefill(params, b_s1, max_seq=S + 9)
    a = np.asarray(ld, np.float32)
    b = np.asarray(lf, np.float32)
    np.testing.assert_allclose(a, b, rtol=2e-3,
                               atol=2e-3 * np.abs(b).max())


def test_unknown_layer_pattern_raises():
    """A misspelt layer pattern fails where the model is built, naming the
    known ones, instead of building a dense stack."""
    cfg = dataclasses.replace(reduced(get_config("rwkv6-3b")),
                              layer_pattern="rwkv7")
    with pytest.raises(ValueError, match="rwkv7") as err:
        Transformer(cfg)
    for known in ("full", "griffin", "nemotron_h", "rwkv"):
        assert repr(known) in str(err.value)


@pytest.mark.parametrize("name", ["internlm2-20b", "recurrentgemma-2b",
                                  "rwkv6-3b", "nemotron3-nano-30b-a3b"])
def test_exact_prompts_match_padding(name):
    """One decode step after a prompt prefilled at its exact length, and
    after the same prompt right-padded to the serving engine's bucket (its
    real last token picked by ``last_pos``): the next-token logits agree
    exactly where the stack says padding is harmless (``EXACT_PROMPTS``
    False), and differ where the cache carries recurrent state."""
    from repro.serve import bucket_len
    cfg = reduced(get_config(name))
    m = Transformer(cfg)
    # no leaf at its zero init: Griffin's conv starts at zero, and with it
    # the input of its recurrence
    leaves, treedef = jax.tree.flatten(m.init(jax.random.key(2)))
    keys = jax.random.split(jax.random.key(3), len(leaves))
    params = treedef.unflatten([
        p + 0.1 * jax.random.normal(k, p.shape, p.dtype)
        for p, k in zip(leaves, keys)])
    B, L, max_seq = 2, 11, 32
    padded = bucket_len(L, max_seq, exact=False)
    assert padded > L
    toks = RNG.integers(1, cfg.vocab, (B, L + 1))
    prompt = np.zeros((B, padded), np.int32)
    prompt[:, :L] = toks[:, :L]
    nxt = {"tokens": jnp.asarray(toks[:, L], jnp.int32)}
    pos = jnp.full((B,), L, jnp.int32)

    logits = []
    for batch, last in ((toks[:, :L], None), (prompt, [L - 1] * B)):
        _, cache = m.prefill(params, {"tokens": jnp.asarray(batch,
                                                            jnp.int32)},
                             max_seq=max_seq,
                             last_pos=None if last is None
                             else jnp.asarray(last, jnp.int32))
        ld, _ = m.decode_step(params, cache, nxt, pos)
        logits.append(np.asarray(ld, np.float32))
    exact, pad = logits
    agree = np.allclose(pad, exact, rtol=2e-3,
                        atol=2e-3 * np.abs(exact).max())
    assert agree == (not m.stack.EXACT_PROMPTS), (
        name, np.abs(pad - exact).max())


def test_rwkv_donated_decode_steps_match_prefill():
    """Eight donated decode steps in a row, teacher-forced, after
    prefill(S): step i's logits == last logits of prefill(S + i + 1).  The
    state pool is carried and updated in place across steps, not only
    within one."""
    cfg = reduced(get_config("rwkv6-3b"))
    m = Transformer(cfg)
    params = m.init(jax.random.key(1))
    B, S, n = 2, 24, 8
    toks = jnp.asarray(RNG.integers(0, cfg.vocab, (B, S + n)), jnp.int32)
    _, cache = m.prefill(params, {"tokens": toks[:, :S]}, max_seq=S + n)
    decode = jax.jit(m.decode_step, donate_argnums=(1,))
    for i in range(n):
        ld, cache = decode(params, cache, {"tokens": toks[:, S + i]},
                           jnp.full((B,), S + i, jnp.int32))
        lf, _ = m.prefill(params, {"tokens": toks[:, :S + i + 1]},
                          max_seq=S + n)
        a = np.asarray(ld, np.float32)
        b = np.asarray(lf, np.float32)
        np.testing.assert_allclose(a, b, rtol=2e-3,
                                   atol=2e-3 * np.abs(b).max(),
                                   err_msg=f"decode step {i}")


@pytest.mark.parametrize("hs,H,B", [(16, 4, 2), (64, 3, 3)])
@pytest.mark.parametrize("form", ["kernel", "jnp"])
def test_packed_wkv6_decode_matches_unpacked_update(hs, H, B, form):
    """Four one-token steps on layer 1 of a packed state pool, by the
    Pallas kernel (interpret mode; 3 rows take tiles of 1) and by the
    packed jnp form, against ``_wkv_with_state`` on the unpacked (hs, hs)
    state: outputs and states agree to fp32 rounding, and the other layers
    are left as they were."""
    from repro.kernels import ops, ref
    from repro.models.rwkv6 import _wkv_with_state, pack_state, unpack_state
    L = 3
    rng = np.random.default_rng(hs)

    def draw(shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    s = draw((B, H, hs, hs))
    pool = pack_state(draw((L, B, H, hs, hs))).at[1].set(pack_state(s))
    others = np.asarray(pool)[[0, 2]]
    u = draw((H, hs))
    step = (functools.partial(ops.wkv6_decode, interpret=True)
            if form == "kernel" else ref.wkv6_decode_ref)
    for i in range(4):
        r, k, v = draw((B, H, hs)), draw((B, H, hs)), draw((B, H, hs))
        w = jnp.asarray(rng.uniform(0.2, 0.99, (B, H, hs)), jnp.float32)
        o_want, s = _wkv_with_state(r[:, None], k[:, None], v[:, None],
                                    w[:, None], u, s)
        o, pool = step(pool, jnp.int32(1), r, k, v, w, u)
        np.testing.assert_allclose(
            np.asarray(o), np.asarray(o_want[:, 0]), rtol=1e-5,
            atol=1e-5 * float(jnp.abs(o_want).max()), err_msg=f"step {i}")
        np.testing.assert_allclose(
            np.asarray(unpack_state(pool[1], hs)), np.asarray(s), rtol=1e-5,
            atol=1e-5 * float(jnp.abs(s).max()), err_msg=f"step {i}")
    np.testing.assert_array_equal(np.asarray(pool)[[0, 2]], others)


@pytest.mark.parametrize("hs", [16, 64, 96, 128])
def test_pack_state_round_trip(hs):
    """Packing is the row-major reshape to 128 lanes where hs divides 128
    (lane n of row p holds S[p·g + n // hs, n % hs], g = 128 / hs), the
    state as it is otherwise, and unpacking undoes it."""
    from repro.models.rwkv6 import pack_state, state_shape, unpack_state
    s = np.arange(2 * 3 * hs * hs, dtype=np.float32).reshape(2, 3, hs, hs)
    p = pack_state(jnp.asarray(s))
    packs = 128 % hs == 0 and hs < 128
    assert p.shape[2:] == state_shape(hs) == (
        (hs * hs // 128, 128) if packs else (hs, hs))
    np.testing.assert_array_equal(np.asarray(unpack_state(p, hs)), s)
    if packs:
        g = 128 // hs
        rows, lanes = np.meshgrid(np.arange(p.shape[2]), np.arange(128),
                                  indexing="ij")
        np.testing.assert_array_equal(
            np.asarray(p), s[:, :, rows * g + lanes // hs, lanes % hs])


def test_moe_decode_matches_prefill_no_dropping():
    """MoE consistency holds exactly when capacity never drops (the
    residual mismatch under dropping is the documented GShard behavior)."""
    cfg = dataclasses.replace(reduced(get_config("qwen3-moe-30b-a3b")),
                              capacity_factor=1000.0)
    m = Transformer(cfg)
    params = m.init(jax.random.key(1))
    B, S = 2, 16
    toks = RNG.integers(0, cfg.vocab, (B, S + 1))
    b_s = {"tokens": jnp.asarray(toks[:, :S], jnp.int32)}
    b_s1 = {"tokens": jnp.asarray(toks, jnp.int32)}
    nxt = {"tokens": jnp.asarray(toks[:, S], jnp.int32)}
    _, cache = m.prefill(params, b_s, max_seq=S + 4)
    ld, _ = m.decode_step(params, cache, nxt, jnp.full((B,), S, jnp.int32))
    lf, _ = m.prefill(params, b_s1, max_seq=S + 5)
    np.testing.assert_allclose(np.asarray(ld), np.asarray(lf), rtol=2e-3,
                               atol=2e-3 * np.abs(np.asarray(lf)).max())


def test_moe_vs_dense_oracle():
    """Capacity-∞ MoE == explicit per-token expert loop."""
    from repro.models.moe import moe_apply
    from repro.models.layers import init_tree
    from repro.models.moe import moe_spec

    cfg = dataclasses.replace(reduced(get_config("qwen3-moe-30b-a3b")),
                              capacity_factor=1000.0)
    spec = moe_spec(cfg)
    params = init_tree(spec, jax.random.key(3), jnp.float32)
    x = jnp.asarray(RNG.standard_normal((2, 8, cfg.d_model))
                    .astype(np.float32))
    out, aux = moe_apply(params, x, cfg)

    # oracle: per-token dense loop
    logits = x.reshape(-1, cfg.d_model) @ params["router"]
    probs = jax.nn.softmax(logits, -1)
    g, idx = jax.lax.top_k(probs, cfg.top_k)
    g = g / g.sum(-1, keepdims=True)
    xf = x.reshape(-1, cfg.d_model)
    want = np.zeros_like(np.asarray(xf))
    ew = params["experts"]
    for t in range(xf.shape[0]):
        for j in range(cfg.top_k):
            e = int(idx[t, j])
            h = jax.nn.silu(xf[t] @ ew["w_gate"][e]) * (xf[t] @ ew["w_up"][e])
            want[t] += float(g[t, j]) * np.asarray(h @ ew["w_down"][e])
    np.testing.assert_allclose(np.asarray(out.reshape(-1, cfg.d_model)),
                               want, rtol=2e-3, atol=2e-3)
    assert float(aux) > 0


def test_musicgen_multihead_shapes():
    cfg = reduced(get_config("musicgen-large"))
    m = Transformer(cfg)
    params = m.init(jax.random.key(0))
    B, S = 2, 16
    batch = _batch(cfg, B, S)
    logits, cache = m.prefill(params, batch, max_seq=S + 4)
    assert logits.shape == (B, cfg.n_codebooks, cfg.vocab)


def test_rwkv_long_context_state_is_constant_memory():
    """Attention-free arch: cache size is independent of sequence length —
    the reason long_500k runs for rwkv6/recurrentgemma only."""
    cfg = reduced(get_config("rwkv6-3b"))
    m = Transformer(cfg)
    c1 = jax.eval_shape(lambda: m.init_cache(1, 1_000))
    c2 = jax.eval_shape(lambda: m.init_cache(1, 500_000))
    def sz(t):
        return sum(np.prod(x.shape) for x in jax.tree.leaves(t))
    assert sz(c1) == sz(c2)


def test_kv_quant_decode_close_to_fp():
    """int8 KV cache (§Perf 'kvq8'): greedy-decode logits stay close to the
    bf16 cache over multiple steps."""
    cfg = reduced(get_config("internlm2-20b"))
    m = Transformer(cfg)
    mq = Transformer(cfg, kv_quant=True)
    params = m.init(jax.random.key(0))
    B = 2
    toks = RNG.integers(0, cfg.vocab, (B, 8))
    cache, cacheq = m.init_cache(B, 16), mq.init_cache(B, 16)
    assert cacheq["k"].dtype == jnp.int8
    err = 0.0
    for t in range(8):
        tok = {"tokens": jnp.asarray(toks[:, t], jnp.int32)}
        pos = jnp.full((B,), t, jnp.int32)
        l1, cache = m.decode_step(params, cache, tok, pos)
        l2, cacheq = mq.decode_step(params, cacheq, tok, pos)
        err = max(err, float(np.max(np.abs(
            np.asarray(l1, np.float32) - np.asarray(l2, np.float32)))))
    assert err < 0.25, err
    # k/v bytes shrink by the dtype itemsize (bf16→int8: 2×; fp32→int8: 4×)
    def sz(c):
        return sum(int(np.prod(x.shape)) * x.dtype.itemsize
                   for k, x in c.items() if k in ("k", "v"))
    ratio = np.dtype(cfg.dtype).itemsize
    assert sz(cacheq) * ratio == sz(cache)
