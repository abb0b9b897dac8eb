"""Plain float32 reference of a Nemotron-H language model (NVIDIA
Nemotron-3 Nano 30B-A3B, model type ``nemotron_h``), and the benchmark's
seeded weights for it.

The reference imports nothing of the program.  It runs the whole model
over whole token sequences (the prompt followed by the served tokens) in
straightforward ``jax.numpy``, one block at a time, with every matrix
product at full float32 precision.  Each block is
``x + mixer(rmsnorm(x))``, the mixer named by its letter in
``hybrid_override_pattern``:

    M, Mamba-2     z, xBC, dt = x W_in;  xBC = silu(conv1d(xBC) + b)
                   x, B, C = split(xBC)  (B, C: n_groups groups of
                   ssm_state_size, each shared by heads/n_groups heads)
                   dt = softplus(dt + dt_bias);  A = -exp(A_log)
                   per head  h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T
                             y_t = h_t C_t + D x_t
                   out = rmsnorm_grouped(y * silu(z)) W_out
    E, MoE         s = sigmoid(x W_r);  the top k experts on s + bias;
                   weights s at those, over their sum, times
                   routed_scaling_factor;  out = sum over the chosen held
                   experts of weight * relu(x W_up)^2 W_down, plus the
                   shared expert relu(x W_up')^2 W_down'
    *, attention   grouped-query causal softmax attention, no positional
                   embedding

Departures, and choices the published config leaves open: the SSM runs as
a sequential recurrence over tokens (the published model computes it in
chunks; the sums are the same); the attention has no rotary embedding
(``rope_theta`` is read as unused); every held expert is applied to every
token and weighted by the routing (weight 0 where the token did not
choose it), so the result does not depend on a dispatch.  The chip holds
the experts ``[expert_offset, expert_offset + n_routed_experts)`` of the
``n_routed_experts_published`` that the router scores; a chosen expert
that is not held adds nothing, and the weights are still normalised over
all k chosen.

Weights (``init``): random, from a seed, at scales that keep every term
of the equations live and the residual stream at unit scale (embedding
N(0, 1)), as ``rwkv6.py``'s init explains a comparison needs: -A =
exp(``A_log``) log-uniform in (1, 8) and ``dt_bias`` = softplus^-1 of a
log-uniform dt in (0.005, 0.1), so per-token decays lie between about
0.45 and 0.995;
``D`` from U(0, 1); the conv bias from N(0, 0.1^2); a correction bias
N(0, 0.05^2) that moves some choices without fixing them (router scores
spread by some 0.2); and each mixer's output projection at
``RESIDUAL_GAIN`` of unit gain.
"""
from __future__ import annotations

import functools

import numpy as np

RESIDUAL_GAIN = 0.1
KINDS = {"M": "mamba2", "E": "moe", "*": "attn"}


def sizes(cfg):
    """The sizes the reference reads, by the published config's keys."""
    H, Pd = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N = cfg["n_groups"], cfg["ssm_state_size"]
    return {"d": cfg["hidden_size"], "V": cfg["vocab_size"], "H": H,
            "P": Pd, "G": G, "N": N, "di": H * Pd,
            "cd": H * Pd + 2 * G * N, "K": cfg["conv_kernel"],
            "E": cfg["n_routed_experts_published"],
            "Eh": cfg["n_routed_experts"], "e0": cfg["expert_offset"],
            "k": cfg["num_experts_per_tok"],
            "f": cfg["moe_intermediate_size"],
            "fs": cfg["moe_shared_expert_intermediate_size"],
            "nh": cfg["num_attention_heads"],
            "kv": cfg["num_key_value_heads"], "dh": cfg["head_dim"],
            "eps": cfg["layer_norm_epsilon"],
            "scale": cfg["routed_scaling_factor"],
            "kinds": [KINDS[c] for c in cfg["hybrid_override_pattern"]]}


def layout(cfg):
    """Leaf shapes and their init, in the program's parameter tree:
    {path: (shape, kind, arg)}."""
    s = sizes(cfg)
    d = s["d"]
    mixers = {
        "mamba2": {
            "in_proj": ((d, s["di"] + s["cd"] + s["H"]), "normal",
                        1.0 / np.sqrt(d)),
            "conv_w": ((s["K"], s["cd"]), "normal", 1.0 / np.sqrt(s["K"])),
            "conv_b": ((s["cd"],), "normal", 0.1),
            "dt_bias": ((s["H"],), "dt_bias", (0.005, 0.1)),
            "A_log": ((s["H"],), "log_uniform", (1.0, 8.0)),
            "D": ((s["H"],), "uniform", (0.0, 1.0)),
            "norm": ((s["di"],), "ones", None),
            "out_proj": ((s["di"], d), "normal",
                         RESIDUAL_GAIN / np.sqrt(s["di"]))},
        "moe": {
            "router": ((d, s["E"]), "normal", 1.0 / np.sqrt(d)),
            "router_bias": ((s["E"],), "normal", 0.05),
            "experts": {
                "w_up": ((s["Eh"], d, s["f"]), "normal", 1.0 / np.sqrt(d)),
                "w_down": ((s["Eh"], s["f"], d), "normal",
                           RESIDUAL_GAIN / np.sqrt(s["f"]))},
            "shared": {
                "w_up": ((d, s["fs"]), "normal", 1.0 / np.sqrt(d)),
                "w_down": ((s["fs"], d), "normal",
                           RESIDUAL_GAIN / np.sqrt(s["fs"]))}},
        "attn": {
            "w_q": ((d, s["nh"], s["dh"]), "normal", 1.0 / np.sqrt(d)),
            "w_k": ((d, s["kv"], s["dh"]), "normal", 1.0 / np.sqrt(d)),
            "w_v": ((d, s["kv"], s["dh"]), "normal", 1.0 / np.sqrt(d)),
            "w_o": ((s["nh"], s["dh"], d), "normal",
                    RESIDUAL_GAIN / np.sqrt(s["nh"] * s["dh"]))},
    }
    return {"embed": ((s["V"], d), "normal", 1.0),
            "head": ((d, s["V"]), "normal", 1.0 / np.sqrt(d)),
            "final_norm": ((d,), "ones", None),
            "blocks": [{"norm": ((d,), "ones", None), kind: mixers[kind]}
                       for kind in s["kinds"]]}


def _is_leaf(x):
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[1], str)


def init(cfg, seed: int, dtype="bfloat16"):
    """The weights, made on the default device from ``seed`` (one jitted
    call per leaf shape and init), in ``dtype``."""
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree.flatten(layout(cfg), is_leaf=_is_leaf)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    dt = jnp.dtype(dtype)
    out = [_make(shape, kind, arg, dt)(k)
           for k, (shape, kind, arg) in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, out)


@functools.lru_cache(maxsize=None)
def _make(shape, kind, arg, dt):
    import jax
    import jax.numpy as jnp

    def make(key):
        if kind == "ones":
            return jnp.ones(shape, dt)
        if kind == "normal":
            return (jax.random.normal(key, shape, jnp.float32)
                    * arg).astype(dt)
        u = jax.random.uniform(key, shape, jnp.float32)
        lo, hi = arg
        if kind == "uniform":
            return (lo + (hi - lo) * u).astype(dt)
        v = jnp.exp(np.log(lo) + (np.log(hi) - np.log(lo)) * u)
        if kind == "log_uniform":          # A_log = log of U-log(lo, hi)
            return jnp.log(v).astype(dt)
        return (v + jnp.log(-jnp.expm1(-v))).astype(dt)   # softplus^-1

    return jax.jit(make)


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------

def _fp8(x):
    """Round to float8 e4m3 with one scale per tensor (the control's
    precision: the step below the configuration's bfloat16)."""
    import jax.numpy as jnp
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = 448.0 / amax
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def _mm(a, b, quant, spec="...i,ij->...j"):
    import jax.numpy as jnp
    if quant:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision="highest")


def _rms(x, w, eps):
    import jax
    import jax.numpy as jnp
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _mamba(p, x, s, quant):
    """(B, T, d) -> (B, T, d); the recurrence one token at a time."""
    import jax
    import jax.numpy as jnp
    B, T, _ = x.shape
    H, Pd, G, N, di = s["H"], s["P"], s["G"], s["N"], s["di"]
    proj = _mm(x, p["in_proj"], quant)
    z, xbc, dt = proj[..., :di], proj[..., di:di + s["cd"]], proj[..., -H:]
    K = s["K"]
    padded = jnp.concatenate([jnp.zeros((B, K - 1, s["cd"])), xbc], axis=1)
    conv = p["conv_b"] + sum(padded[:, j:j + T] * p["conv_w"][j]
                             for j in range(K))
    xbc = jax.nn.silu(conv)
    xs = xbc[..., :di].reshape(B, T, H, Pd)
    rep = H // G
    bm = jnp.repeat(xbc[..., di:di + G * N].reshape(B, T, G, N), rep, 2)
    cm = jnp.repeat(xbc[..., di + G * N:].reshape(B, T, G, N), rep, 2)
    dt = jax.nn.softplus(dt + p["dt_bias"])                 # (B, T, H)
    a = -jnp.exp(p["A_log"])

    def step(h, inp):
        x_t, b_t, c_t, dt_t = inp
        h = (jnp.exp(dt_t * a)[..., None, None] * h
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        y = jnp.einsum("bhpn,bhn->bhp", h, c_t, precision="highest")
        return h, y

    h0 = jnp.zeros((B, H, Pd, N), jnp.float32)
    _, y = jax.lax.scan(step, h0, tuple(jnp.moveaxis(t, 1, 0)
                                        for t in (xs, bm, cm, dt)))
    y = jnp.moveaxis(y, 0, 1) + p["D"][:, None] * xs
    g = y.reshape(B, T, di) * jax.nn.silu(z)
    g = g.reshape(B, T, G, di // G)
    g = _rms(g, 1.0, s["eps"]).reshape(B, T, di) * p["norm"]
    return _mm(g, p["out_proj"], quant)


def route(p, x, s, quant=False):
    """The k chosen experts of each token and their weights."""
    import jax
    import jax.numpy as jnp
    score = jax.nn.sigmoid(_mm(x, p["router"], quant))      # (..., E)
    _, idx = jax.lax.top_k(score + p["router_bias"], s["k"])
    w = jnp.take_along_axis(score, idx, axis=-1)
    return w / (w.sum(-1, keepdims=True) + 1e-20) * s["scale"], idx


def moe(p, x, s, quant=False):
    """The MoE mixer: the held experts' share and the shared expert."""
    import jax
    import jax.numpy as jnp
    w, idx = route(p, x, s, quant)
    out = _mm(jnp.square(jax.nn.relu(_mm(x, p["shared"]["w_up"], quant))),
              p["shared"]["w_down"], quant)
    for e in range(s["Eh"]):
        weight = jnp.sum(jnp.where(idx == s["e0"] + e, w, 0.0), axis=-1)
        h = jnp.square(jax.nn.relu(_mm(x, p["experts"]["w_up"][e], quant)))
        out = out + weight[..., None] * _mm(h, p["experts"]["w_down"][e],
                                            quant)
    return out


def _attn(p, x, s, quant):
    import jax
    import jax.numpy as jnp
    B, T, _ = x.shape
    q = _mm(x, p["w_q"], quant, "btd,dhk->bthk")
    k = _mm(x, p["w_k"], quant, "btd,dhk->bthk")
    v = _mm(x, p["w_v"], quant, "btd,dhk->bthk")
    rep = s["nh"] // s["kv"]
    k, v = jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)
    att = jnp.einsum("bthk,bshk->bhts", q, k,
                     precision="highest") / np.sqrt(s["dh"])
    causal = jnp.tril(jnp.ones((T, T), bool))
    att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
    o = jnp.einsum("bhts,bshk->bthk", att, v, precision="highest")
    return _mm(o, p["w_o"], quant, "bthk,hkd->btd")


@functools.lru_cache(maxsize=None)
def _jitted(kind: str, frozen, quant: bool):
    """One block of ``kind`` over x, its weights cast to float32 inside the
    program (one compile for every block of a kind)."""
    import jax
    import jax.numpy as jnp
    s = dict(frozen)
    mixer = {"mamba2": _mamba, "moe": moe, "attn": _attn}[kind]

    def block(lp, x):
        lp = jax.tree.map(lambda t: t.astype(jnp.float32), lp)
        return x + mixer(lp[kind], _rms(x, lp["norm"], s["eps"]), s, quant)

    return jax.jit(block)


@functools.lru_cache(maxsize=None)
def _head(eps: float, quant: bool):
    import jax
    import jax.numpy as jnp

    def head(x, idx_b, idx_t, final_norm, w):
        h = _rms(x[idx_b, idx_t], final_norm.astype(jnp.float32), eps)
        return _mm(h, w.astype(jnp.float32), quant)

    return jax.jit(head)


def logits_at(weights, tokens, positions, *, cfg, quant: bool = False):
    """Float32 logits of the reference at the given positions.

    ``weights`` is the parameter tree (device or host arrays, any float
    dtype); ``tokens`` is (B, T) int32, right-padded where sequences are
    shorter (the model is causal, so padding changes no earlier
    position); ``positions`` is a list, per sequence, of the positions
    whose logits are wanted.  Returns a list of (len(positions[b]), vocab)
    float32 numpy arrays.  The stack runs one block at a time."""
    import jax.numpy as jnp
    s = sizes(cfg)
    frozen = tuple((k, v) for k, v in s.items() if k != "kinds")
    x = jnp.asarray(weights["embed"])[jnp.asarray(tokens)].astype(
        jnp.float32)
    for kind, lp in zip(s["kinds"], weights["blocks"]):
        x = _jitted(kind, frozen, bool(quant))(lp, x)
    idx_b = np.concatenate([np.full(len(p), b) for b, p in
                            enumerate(positions)]).astype(np.int32)
    idx_t = np.concatenate([np.asarray(p) for p in positions]).astype(
        np.int32)
    logits = np.asarray(_head(s["eps"], bool(quant))(
        x, idx_b, idx_t, jnp.asarray(weights["final_norm"]),
        jnp.asarray(weights["head"])))
    out, k = [], 0
    for p in positions:
        out.append(logits[k:k + len(p)])
        k += len(p)
    return out


def served_gaps(ref_logits, served):
    """Per served token, how far its reference logit lies below the
    reference's best at that position."""
    ref = np.asarray(ref_logits, np.float64)
    got = ref[np.arange(len(served)), np.asarray(served)]
    return ref.max(axis=-1) - got


# ---------------------------------------------------------------------------
# work counts
# ---------------------------------------------------------------------------

def _block_params(cfg):
    """Parameters of one block of each kind (its norm and its mixer), as
    this chip holds it: {kind: count}."""
    out = {}
    for block in layout(cfg)["blocks"]:
        kind = next(k for k in block if k != "norm")
        out[kind] = sum(int(np.prod(shape)) for _, (shape, _, _) in
                        _flat_items(block))
    return out


def param_count(cfg):
    """Parameters this chip holds (its experts only)."""
    return sum(int(np.prod(shape)) for _, (shape, _, _) in
               _flat_items(layout(cfg)))


def _mamba_flops(s):
    """Operations of one Mamba-2 mixer per token: its products (in, out),
    the conv, and the recurrence (decay, outer product, read-out: about
    six per state element)."""
    return (2 * s["d"] * (s["di"] + s["cd"] + s["H"]) + 2 * s["di"] * s["d"]
            + 2 * s["K"] * s["cd"] + 6 * s["H"] * s["P"] * s["N"])


def _moe_flops(s):
    """Operations of one MoE layer per token: the router, the shared
    expert, and the routed experts' share a token sends to this chip on
    average (k of the E experts, Eh of them held)."""
    expert = 2 * 2 * s["d"] * s["f"]
    return (2 * s["d"] * s["E"] + 2 * 2 * s["d"] * s["fs"]
            + expert * s["k"] * s["Eh"] / s["E"])


def _attn_flops(s):
    """Operations of one attention layer per token: its projections (the
    scores against the cache grow with the context and are left out)."""
    return 2 * s["d"] * (2 * s["nh"] + 2 * s["kv"]) * s["dh"]


def flops_per_token(cfg):
    """Operations of one token's forward pass on this chip."""
    s = sizes(cfg)
    per = {"mamba2": _mamba_flops(s), "moe": _moe_flops(s),
           "attn": _attn_flops(s)}
    return (sum(per[k] for k in s["kinds"]) + 2 * s["d"] * s["V"])


def state_bytes_per_slot(cfg, state_itemsize=4, conv_itemsize=2):
    """Bytes of one request's recurrent state: per Mamba-2 block, the SSM
    state (heads x head_dim x state, float32) and the conv window of the
    last conv_kernel - 1 inputs."""
    s = sizes(cfg)
    n = s["kinds"].count("mamba2")
    return n * (s["H"] * s["P"] * s["N"] * state_itemsize
                + (s["K"] - 1) * s["cd"] * conv_itemsize)


def decode_work(cfg, active: float, weight_itemsize=2):
    """Least work of one decode step over ``active`` rows, per block kind
    that a per-layer metric reads: {kind: {"bytes", "flops"}}.  Mamba-2:
    its weights read once and each active row's SSM state and conv window
    read and written.  MoE: the held experts', the shared expert's and the
    router's weights read once."""
    s = sizes(cfg)
    params = _block_params(cfg)
    n = {k: s["kinds"].count(k) for k in ("mamba2", "moe")}
    mamba_bytes = (n["mamba2"] * params["mamba2"] * weight_itemsize
                   + 2 * active * state_bytes_per_slot(cfg))
    moe_bytes = n["moe"] * params["moe"] * weight_itemsize
    return {"mamba2": {"bytes": mamba_bytes,
                      "flops": n["mamba2"] * active * _mamba_flops(s)},
            "moe": {"bytes": moe_bytes,
                    "flops": n["moe"] * active * _moe_flops(s)}}


def _flat_items(tree, prefix=()):
    items = tree.items() if isinstance(tree, dict) else enumerate(tree)
    for k, v in items:
        if _is_leaf(v):
            yield prefix + (k,), v
        else:
            yield from _flat_items(v, prefix + (k,))
