"""Nemotron-H (Mamba-2, held-expert MoE and attention in one stack) against
the benchmark's plain float32 reference, ``bench/ref/nemotron_h.py``, on
seeded random weights at small sizes."""
import dataclasses
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import Transformer
from repro.models import moe as moe_mod
from repro.serve import Engine, KVSlotPool, Request, ServeRuntime


def _load_ref():
    path = Path(__file__).resolve().parents[1] / "bench" / "ref" / \
        "nemotron_h.py"
    spec = importlib.util.spec_from_file_location("nemotron_h_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load_ref()
RNG = np.random.default_rng(11)


def small(**kw):
    """The registry's smoke variant (every block kind) holding experts
    [4, 8) of 8, in float32."""
    cfg = reduced(get_config("nemotron3-nano-30b-a3b"))
    return dataclasses.replace(
        cfg, **{"experts_held": 4, "expert_offset": 4, **kw})


def ref_cfg(cfg):
    """The program's configuration under the published config's keys."""
    return {"hidden_size": cfg.d_model, "vocab_size": cfg.vocab,
            "mamba_num_heads": cfg.mamba_heads,
            "mamba_head_dim": cfg.mamba_head_dim,
            "n_groups": cfg.ssm_groups, "ssm_state_size": cfg.ssm_state,
            "conv_kernel": cfg.mamba_conv,
            "n_routed_experts_published": cfg.n_experts,
            "n_routed_experts": cfg.n_held,
            "expert_offset": cfg.expert_offset,
            "num_experts_per_tok": cfg.top_k,
            "moe_intermediate_size": cfg.d_ff,
            "moe_shared_expert_intermediate_size": cfg.moe_shared_ff,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.d_head,
            "layer_norm_epsilon": cfg.norm_eps,
            "routed_scaling_factor": cfg.routed_scaling,
            "hybrid_override_pattern": cfg.block_pattern}


def _ref_logits(rc, weights, seqs, positions):
    T = max(len(s) for s in seqs)
    tokens = np.zeros((len(seqs), T), np.int32)
    for b, s in enumerate(seqs):
        tokens[b, :len(s)] = s
    return REF.logits_at(weights, tokens, positions, cfg=rc)


def test_param_tree_matches_reference_layout():
    cfg = small()
    rc = ref_cfg(cfg)
    prog = jax.eval_shape(Transformer(cfg).init, jax.random.key(0))
    ref = jax.eval_shape(lambda: REF.init(rc, 0, "float32"))
    assert jax.tree.structure(prog) == jax.tree.structure(ref)
    assert jax.tree.map(lambda t: t.shape, prog) == \
        jax.tree.map(lambda t: t.shape, ref)
    from repro.configs import param_count
    assert param_count(cfg) == REF.param_count(rc)


def test_prefill_then_pooled_decode_matches_reference():
    """Prefill a prompt longer than one SSD chunk, insert it into a
    KVSlotPool row, then decode teacher-forced with the pool donated: the
    logits at every position agree with the reference's full forward.
    Tolerance: both compute in float32; the program's chunked SSD and its
    fused dispatch sum in another order than the reference's sequential
    recurrence and dense experts, some 1e-6 of the logits' scale."""
    cfg = small()
    rc = ref_cfg(cfg)
    weights = REF.init(rc, 5, "float32")
    m = Transformer(cfg)
    S, n, max_seq = 140, 6, 160
    toks = RNG.integers(0, cfg.vocab, S + n).astype(np.int32)
    pool = KVSlotPool(m, 3, max_seq)
    pool.alloc()
    slot = pool.alloc()
    logits, cache = m.prefill(weights, {"tokens": jnp.asarray(toks[None, :S])},
                              max_seq=max_seq)
    pool.insert(cache, 0, slot)
    got = [np.asarray(logits[0])]
    decode = jax.jit(m.decode_step, donate_argnums=(1,))
    for i in range(n - 1):
        tok = np.zeros(3, np.int32)
        tok[slot] = toks[S + i]
        pos = np.zeros(3, np.int32)
        pos[slot] = S + i
        lg, pool.cache = decode(weights, pool.cache,
                                {"tokens": jnp.asarray(tok)},
                                jnp.asarray(pos))
        got.append(np.asarray(lg[slot]))
    want = _ref_logits(rc, weights, [toks[:S + n - 1]],
                       [np.arange(S - 1, S + n - 1)])[0]
    np.testing.assert_allclose(np.stack(got), want, rtol=0,
                               atol=2e-5 * np.abs(want).max())


def _step_before(p, x, cfg, pool, i, window):
    """``mamba2_step`` as it was before the pooled kernel: block i's
    state sliced out of the pool, updated, read out with an einsum and
    written back."""
    from repro.models import mamba2 as m2
    z, xbc, dt = m2._split_in(p, x, cfg)
    xbc, window = m2._conv1d({"conv_w": p["conv_w"], "conv_b": p["conv_b"]},
                             xbc, cfg.mamba_conv, state=window)
    xs, b, c = m2._split_xbc(jax.nn.silu(xbc[:, 0]), cfg)
    dt = jax.nn.softplus(dt[:, 0] + p["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["A_log"].astype(jnp.float32))
    b, c = m2._heads(b, cfg), m2._heads(c, cfg)
    ssm = (jnp.exp(dt * a)[..., None, None] * pool[i]
           + (dt[..., None] * xs)[..., None] * b[:, :, None, :])
    y = jnp.einsum("bhpn,bhn->bhp", ssm, c, precision="highest")
    y = y + p["D"].astype(jnp.float32)[:, None] * xs
    return (m2._out(p, y[:, None], z, cfg, x.dtype), pool.at[i].set(ssm),
            window)


@pytest.mark.parametrize("path", ["kernel", "reference"])
def test_pooled_mamba2_step_matches_slice_and_write_back(path, monkeypatch):
    """One ``mamba2_step`` against block 1 of a two-block pool, through
    the Pallas kernel (interpret mode, put in the reference's place, which
    the CPU compile target picks) or through the reference: the output,
    the window and block 1's state agree with the slice-update-write-back
    form to float32 rounding, and block 0 is left bit for bit."""
    from repro.kernels import ops as kops
    from repro.kernels import ref as kref
    from repro.models import mamba2 as m2
    calls = []
    if path == "kernel":
        def kernel(*args):
            calls.append(1)
            return kops.mamba2_decode(*args, interpret=True)
        monkeypatch.setattr(kref, "mamba2_decode_ref", kernel)
    rng = np.random.default_rng(13)   # the module's RNG feeds later tests
    cfg = small()
    p = next(b["mamba2"] for b in REF.init(ref_cfg(cfg), 5, "float32")[
        "blocks"] if "mamba2" in b)
    p = dict(p, A_log=jnp.asarray(rng.uniform(-1, 1, cfg.mamba_heads),
                                  jnp.float32),
             dt_bias=jnp.asarray(rng.uniform(-1, 1, cfg.mamba_heads),
                                 jnp.float32))
    B = 3
    pool = jnp.asarray(rng.standard_normal(
        (2, B, cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_state)),
        jnp.float32)
    window = jnp.asarray(rng.standard_normal(
        (B, cfg.mamba_conv - 1, m2.conv_dim(cfg))), jnp.float32)
    x = jnp.asarray(rng.standard_normal((B, 1, cfg.d_model)), jnp.float32)
    want = _step_before(p, x, cfg, pool, 1, window)
    got = m2.mamba2_step(p, x, cfg, pool, 1, window)
    assert len(calls) == (path == "kernel")
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=0,
                                   atol=2e-6 * float(jnp.abs(w).max()))
    np.testing.assert_array_equal(np.asarray(got[1][0]), np.asarray(pool[0]))


def test_engine_serves_reference_argmax():
    """Prefill, then decode through KVSlotPool inside Engine.run: at every
    served position the token served is the reference's best (the gap
    below the reference's largest logit is within float32 rounding:
    1e-5 of the logits' scale, where a wrong state or a dropped expert
    moves logits by 1e-2 and more)."""
    cfg = small()
    rc = ref_cfg(cfg)
    weights = REF.init(rc, 7, "float32")
    rt = ServeRuntime(cfg, max_seq=200, params=weights)
    eng = Engine(rt, capacity=3)
    lens = [(5, 9), (140, 4), (13, 12), (1, 7), (60, 1)]
    reqs = [Request(rid=i, prompt=RNG.integers(0, cfg.vocab, p).astype(
        np.int32), max_new_tokens=g) for i, (p, g) in enumerate(lens)]
    eng.run(reqs, respect_arrivals=False)
    assert all(len(r.tokens) == r.max_new_tokens for r in reqs)
    seqs = [np.concatenate([r.prompt, r.tokens[:-1]]) for r in reqs]
    positions = [np.arange(r.prompt_len - 1, r.prompt_len - 1 + len(r.tokens))
                 for r in reqs]
    logits = _ref_logits(rc, weights, seqs, positions)
    scale = max(np.abs(lg).max() for lg in logits)
    for r, lg in zip(reqs, logits):
        gaps = REF.served_gaps(lg, r.tokens)
        assert gaps.max() <= 1e-5 * scale, (r.rid, gaps)


def _moe_params(cfg, key, bias=None):
    rc = ref_cfg(cfg)
    p = jax.tree.map(lambda t: t.astype(jnp.float32), REF.init(rc, key,
                                                                "float32"))
    lp = next(b["moe"] for b in p["blocks"] if "moe" in b)
    if bias is not None:
        lp = dict(lp, router_bias=bias)
    return lp, REF.sizes(rc)


def test_held_experts_are_dropless():
    """Every token chooses held expert 4: the held dispatch computes all of
    them, where the capacity of the dropping dispatch would keep 24 of
    the 64."""
    cfg = small()
    bias = jnp.zeros(cfg.n_experts).at[4].set(100.0)
    lp, s = _moe_params(cfg, 3, bias)
    x = jnp.asarray(RNG.standard_normal((2, 32, cfg.d_model)), jnp.float32)
    w, idx = REF.route(lp, x, s)
    assert bool(jnp.all(jnp.any(idx == 4, axis=-1)))
    T = x.shape[0] * x.shape[1]
    assert moe_mod._capacity(T, cfg.n_experts, cfg.top_k,
                             cfg.capacity_factor) < T
    got, _ = moe_mod.moe_held_apply(lp, x, cfg)
    want = REF.moe(lp, x, s)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-5 * float(jnp.abs(want).max()))


def test_sixteen_shares_add_up_to_the_whole_layer():
    """32 experts over 16 chips, 2 held on each: the 16 shares' outputs,
    with the shared expert (which every chip computes) counted once, add
    up to the uncut reference layer."""
    whole = small(n_experts=32, top_k=4, experts_held=0, expert_offset=0)
    lp, s = _moe_params(whole, 9)
    x = jnp.asarray(RNG.standard_normal((2, 24, whole.d_model)),
                    jnp.float32)
    want = REF.moe(lp, x, s)
    shared = REF.moe(dict(lp, experts=jax.tree.map(
        lambda t: t[:0], lp["experts"])), x, dict(s, Eh=0))
    total = jnp.zeros_like(want)
    for j in range(16):
        share = dataclasses.replace(whole, experts_held=2,
                                    expert_offset=2 * j)
        part = dict(lp, experts=jax.tree.map(lambda t: t[2 * j:2 * j + 2],
                                             lp["experts"]))
        out, _ = moe_mod.moe_held_apply(part, x, share)
        total = total + out - shared
    np.testing.assert_allclose(total + shared, want, rtol=0,
                               atol=1e-5 * float(jnp.abs(want).max()))


def test_router_matches_reference():
    """Sigmoid scores, top-k on score + correction bias, weights over
    their sum times the routed scaling: the same experts and weights as
    the reference, on inputs without near-ties."""
    cfg = small(n_experts=16, top_k=6)
    lp, s = _moe_params(cfg, 4)
    x = jnp.asarray(RNG.standard_normal((40, cfg.d_model)), jnp.float32)
    w, idx = moe_mod.sigmoid_route(lp, x, cfg)
    rw, ridx = REF.route(lp, x, s)
    score = jax.nn.sigmoid(x @ lp["router"]) + lp["router_bias"]
    ranked = jnp.sort(score, axis=-1)[:, ::-1]
    assert float(jnp.min(ranked[:, 5] - ranked[:, 6])) > 1e-4  # no near-ties
    np.testing.assert_array_equal(idx, ridx)
    np.testing.assert_allclose(w, rw, rtol=1e-6)
    np.testing.assert_allclose(w.sum(-1), cfg.routed_scaling, rtol=1e-6)


def test_pool_holds_both_cache_kinds():
    """One KVSlotPool holds the SSM state, the conv windows and the KV
    cache: it infers each leaf's batch axis, reports one slot's bytes per
    kind, and an insert writes one row and leaves the others as they
    were."""
    cfg = small(dtype="bfloat16")
    m = Transformer(cfg)
    max_seq, C = 40, 4
    pool = KVSlotPool(m, C, max_seq)
    leaves = jax.tree.leaves_with_path(pool.cache)
    assert [jax.tree_util.keystr(p) for p, _ in leaves] == [
        "['conv']", "['kv']['k']", "['kv']['v']", "['ssm']"]
    assert pool.batch_axes == [1] * 4
    H, P, N = cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_state
    cd = cfg.mamba_inner + 2 * cfg.ssm_groups * N
    n_attn = cfg.layer_kinds().count("attn")
    assert pool.stats()["slot_bytes"] == {
        "ssm": 2 * H * P * N * 4,
        "conv": 2 * (cfg.mamba_conv - 1) * cd * 2,
        "kv": n_attn * max_seq * 2 * cfg.n_kv_heads * cfg.d_head * 2}

    params = m.init(jax.random.key(2))
    toks = jnp.asarray(RNG.integers(0, cfg.vocab, (1, 9)), jnp.int32)
    _, new = m.prefill(params, {"tokens": toks}, max_seq=max_seq)
    before = jax.tree.map(np.asarray, pool.cache)
    for _ in range(3):
        slot = pool.alloc()
    pool.insert(new, 0, slot)
    for (path, got), old, row in zip(jax.tree.leaves_with_path(pool.cache),
                                     jax.tree.leaves(before),
                                     jax.tree.leaves(new)):
        got = np.asarray(got)
        others = [i for i in range(C) if i != slot]
        np.testing.assert_array_equal(got[:, others], old[:, others],
                                      err_msg=jax.tree_util.keystr(path))
        np.testing.assert_array_equal(got[:, slot],
                                      np.asarray(row[:, 0]).astype(got.dtype))


@pytest.mark.parametrize("rope", [True, False])
def test_rope_switch_leaves_other_configs_alone(rope):
    """Attention without rotary embedding is one config value; every
    registered config but nemotron_h keeps it on."""
    from repro.configs import ALL_ARCHS
    assert all(c.rope for c in ALL_ARCHS if c.layer_pattern != "nemotron_h")
    cfg = dataclasses.replace(reduced(get_config("qwen2.5-14b")), rope=rope)
    from repro.models.attention import _project_qkv
    p = Transformer(cfg).init(jax.random.key(0))["layers"]["attn"]
    p = jax.tree.map(lambda t: t[0], p)
    x = jnp.ones((1, 2, cfg.d_model), jnp.float32)       # same input twice
    q, k, _ = _project_qkv(p, x, cfg, jnp.arange(2)[None])
    same = bool(jnp.allclose(q[0, 0], q[0, 1]))
    assert same == (not rope)
