"""Plain float32 reference of an RWKV-6 "Finch" language model
[arXiv:2404.05892], and the benchmark's seeded weights for it.

The reference imports nothing of the program.  It runs the whole model
over whole token sequences (the prompt followed by the served tokens) in
straightforward ``jax.numpy``, one layer at a time, with every matrix
product at full float32 precision.  Its equations are those of the
paper's time mix and channel mix, in the form the program states them:

    token shift      sx_t = x_{t-1}  (x_{-1} = 0),  xx = sx - x
    ddlerp           x_z  = x + xx * (mu_z + tanh((x + xx*mu_x) A_z) B_z)
    projections      r, k, v = x_r W_r, x_k W_k, x_v W_v;  g = silu(x_g W_g)
    decay            w_t = exp(-exp(w0 + tanh(x_w A_w) B_w))
    wkv, per head    o_t = r_t (S_{t-1} + diag(u) k_t^T v_t)
                     S_t = diag(w_t) S_{t-1} + k_t^T v_t
    time-mix out     ((rmsnorm_head(o) * ln_x) * g) W_o
    channel mix      sigmoid(x_r W_r') * (relu(x_k W_k')^2 W_v')

Departures from the paper, all as the program states them: RMSNorm
without bias in place of LayerNorm (the blocks' pre-norms and the final
norm) and in place of the per-head GroupNorm; no extra norm after the
embedding; one LoRA of rank 32 per interpolated input (the paper shares
one five-way projection), and rank 32 for the decay (the paper uses 64).

Weights (``init``): the program's own random init leaves every LoRA B,
every mixing coefficient, the decay base and the bonus at zero, and makes
each layer move the residual stream by about its own size, so bfloat16
rounding adds up over 32 layers until the logits differ from float32 by
half their size.  A comparison against that init could not tell bfloat16
from a lower precision.  This init keeps the residual stream at unit
scale (embedding N(0, 1)), draws the mixing coefficients from U(0, 1), the
decay base from U(-6, 0) (per-token decays from 0.37 to 0.998), the bonus
from U(-0.5, 0.5) and the LoRA B matrices at 0.1-0.5 of unit gain, so
every term of the equations above is live, and scales the two output
projections of each layer (``w_out`` and the channel mix's ``w_v``) to
``RESIDUAL_GAIN`` of unit gain, as trained models move the stream by a
fraction per layer.  The logits come out at about unit scale.
"""
from __future__ import annotations

import functools

import numpy as np

LORA_R = 32
MIX = ("w", "k", "v", "r", "g")
RESIDUAL_GAIN = 0.1
NORM_EPS = 1e-6


def layout(cfg):
    """Leaf shapes and their init, in the program's parameter tree:
    {path: (shape, kind, scale)}."""
    d, f, v, n = cfg["d_model"], cfg["d_ff"], cfg["vocab"], cfg["n_layers"]
    L = (n,)
    tm = {"mu_x": (L + (d,), "uniform", (0.0, 1.0)),
          "w0": (L + (d,), "uniform", (-6.0, 0.0)),
          "u": (L + (d,), "uniform", (-0.5, 0.5)),
          "ln_x": (L + (d,), "ones", None),
          "w_out": (L + (d, d), "normal", RESIDUAL_GAIN / np.sqrt(d))}
    for z in MIX:
        tm[f"mu_{z}"] = (L + (d,), "uniform", (0.0, 1.0))
        tm[f"lora_a_{z}"] = (L + (d, LORA_R), "normal", 1.0 / np.sqrt(d))
        gain = 0.5 if z == "w" else 0.1
        tm[f"lora_b_{z}"] = (L + (LORA_R, d), "normal",
                             gain / np.sqrt(LORA_R))
        if z != "w":
            tm[f"w_{z}"] = (L + (d, d), "normal", 1.0 / np.sqrt(d))
    cm = {"mu_k": (L + (d,), "uniform", (0.0, 1.0)),
          "mu_r": (L + (d,), "uniform", (0.0, 1.0)),
          "w_k": (L + (d, f), "normal", 1.0 / np.sqrt(d)),
          "w_v": (L + (f, d), "normal", RESIDUAL_GAIN / np.sqrt(f)),
          "w_r": (L + (d, d), "normal", 1.0 / np.sqrt(d))}
    return {"embed": ((v, d), "normal", 1.0),
            "head": ((d, v), "normal", 1.0 / np.sqrt(d)),
            "final_norm": ((d,), "ones", None),
            "layers": {"ln1": (L + (d,), "ones", None),
                       "ln2": (L + (d,), "ones", None),
                       "rwkv": {"tm": tm, "cm": cm}}}


def _is_leaf(x):
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[1], str)


def init(cfg, seed: int, dtype="bfloat16"):
    """The weights, made on the default device in one jitted call from
    ``seed``, in ``dtype``."""
    import jax
    import jax.numpy as jnp

    spec = layout(cfg)
    leaves, treedef = jax.tree.flatten(spec, is_leaf=_is_leaf)
    dt = jnp.dtype(dtype)

    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (shape, kind, arg) in zip(keys, leaves):
            if kind == "ones":
                out.append(jnp.ones(shape, dt))
            elif kind == "uniform":
                out.append(jax.random.uniform(k, shape, jnp.float32,
                                              *arg).astype(dt))
            else:
                out.append((jax.random.normal(k, shape, jnp.float32)
                            * arg).astype(dt))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(make)(jax.random.key(seed))


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


def _mm(a, b, quant):
    import jax.numpy as jnp
    if quant:
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision="highest")


def _rms(x, w, eps=NORM_EPS):
    import jax
    import jax.numpy as jnp
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _shift(x):
    import jax.numpy as jnp
    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)


def _layer(lp, x, *, head_size, quant):
    """One block over (B, T, d) float32; ``lp`` is the layer's weights in
    float32."""
    import jax
    import jax.numpy as jnp

    tm, cm = lp["rwkv"]["tm"], lp["rwkv"]["cm"]
    B, T, d = x.shape
    H = d // head_size
    xn = _rms(x, lp["ln1"])
    xx = _shift(xn) - xn
    inner = xn + xx * tm["mu_x"]

    def lerp(z):
        lora = _mm(jnp.tanh(_mm(inner, tm[f"lora_a_{z}"], quant)),
                   tm[f"lora_b_{z}"], quant)
        return xn + xx * (tm[f"mu_{z}"] + lora)

    xw, xk, xv, xr, xg = (lerp(z) for z in MIX)
    r = _mm(xr, tm["w_r"], quant).reshape(B, T, H, head_size)
    k = _mm(xk, tm["w_k"], quant).reshape(B, T, H, head_size)
    v = _mm(xv, tm["w_v"], quant).reshape(B, T, H, head_size)
    g = jax.nn.silu(_mm(xg, tm["w_g"], quant))
    dec = tm["w0"] + _mm(jnp.tanh(_mm(xw, tm["lora_a_w"], quant)),
                         tm["lora_b_w"], quant)
    w = jnp.exp(-jnp.exp(dec)).reshape(B, T, H, head_size)
    u = tm["u"].reshape(H, head_size)

    def step(s, inp):
        r_t, k_t, v_t, w_t = inp                       # (B, H, hs)
        kv = k_t[..., :, None] * v_t[..., None, :]     # (B, H, hs, hs)
        o = jnp.einsum("bhi,bhij->bhj", r_t, s + u[..., :, None] * kv,
                       precision="highest")
        return w_t[..., :, None] * s + kv, o

    s0 = jnp.zeros((B, H, head_size, head_size), jnp.float32)
    _, o = jax.lax.scan(step, s0, tuple(jnp.moveaxis(t, 1, 0)
                                        for t in (r, k, v, w)))
    o = jnp.moveaxis(o, 0, 1)                          # (B, T, H, hs)
    o = _rms(o, 1.0).reshape(B, T, d) * tm["ln_x"]
    h = x + _mm(o * g, tm["w_out"], quant)

    hn = _rms(h, lp["ln2"])
    xx = _shift(hn) - hn
    xk = hn + xx * cm["mu_k"]
    xr = hn + xx * cm["mu_r"]
    kk = jnp.square(jax.nn.relu(_mm(xk, cm["w_k"], quant)))
    return h + jax.nn.sigmoid(_mm(xr, cm["w_r"], quant)) * _mm(
        kk, cm["w_v"], quant)


@functools.lru_cache(maxsize=None)
def _jitted(head_size: int, quant: bool):
    """Layer ``i`` of the stacked weights over x, the layer's weights cast
    to float32 inside the program (one compile for every layer)."""
    import jax
    import jax.numpy as jnp

    def layer(layers, i, x):
        lp = jax.tree.map(lambda t: t[i].astype(jnp.float32), layers)
        return _layer(lp, x, head_size=head_size, quant=quant)

    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _head(quant: bool):
    import jax
    import jax.numpy as jnp

    def head(x, idx_b, idx_t, final_norm, w):
        h = _rms(x[idx_b, idx_t], final_norm.astype(jnp.float32))
        return _mm(h, w.astype(jnp.float32), quant)

    return jax.jit(head)


def logits_at(weights, tokens, positions, *, head_size: int,
              quant: bool = False):
    """Float32 logits of the reference at the given positions.

    ``weights`` is the parameter tree (device or host arrays, any float
    dtype); ``tokens`` is (B, T) int32, right-padded where sequences are
    shorter (the model is causal, so padding changes no earlier
    position); ``positions`` is a list, per sequence, of the positions
    whose logits are wanted.  Returns a list of (len(positions[b]), vocab)
    float32 numpy arrays.  The stack runs one layer at a time, so only one
    layer's float32 weights exist at once."""
    import jax
    import jax.numpy as jnp

    layers = weights["layers"]
    n_layers = layers["ln1"].shape[0]
    x = jnp.asarray(weights["embed"])[jnp.asarray(tokens)].astype(
        jnp.float32)
    fn = _jitted(head_size, bool(quant))
    for i in range(n_layers):
        x = fn(layers, i, x)
    idx_b = np.concatenate([np.full(len(p), b) for b, p in
                            enumerate(positions)]).astype(np.int32)
    idx_t = np.concatenate([np.asarray(p) for p in positions]).astype(
        np.int32)
    logits = np.asarray(_head(bool(quant))(
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


def state_bytes_per_slot(cfg, state_itemsize=4, shift_itemsize=2):
    """Bytes of one request's recurrent state: per layer, one head_size x
    head_size matrix per head and the two token-shift rows."""
    d, n, hs = cfg["d_model"], cfg["n_layers"], cfg["rwkv_head_size"]
    heads = d // hs
    return n * (heads * hs * hs * state_itemsize + 2 * d * shift_itemsize)


def param_count(cfg):
    return sum(int(np.prod(shape)) for _, (shape, _, _) in
               _flat_items(layout(cfg)))


def matmul_params_per_token(cfg):
    """Parameters each token multiplies through (every matrix except the
    embedding, which is a lookup)."""
    total = 0
    for path, (shape, _, _) in _flat_items(layout(cfg)):
        stacked = path[0] == "layers"
        if len(shape) == 2 + stacked and path != ("embed",):
            total += int(np.prod(shape))
    return total


def flops_per_token(cfg):
    """Operations per token of the forward pass: two per weight each
    token multiplies through, plus the wkv recurrence (about four per
    state element per head and token: decay, outer product, bonus, and the
    product with r)."""
    d, n, hs = cfg["d_model"], cfg["n_layers"], cfg["rwkv_head_size"]
    return 2 * matmul_params_per_token(cfg) + 4 * n * d * hs


def _flat_items(tree, prefix=()):
    for k, v in tree.items():
        if _is_leaf(v):
            yield prefix + (k,), v
        else:
            yield from _flat_items(v, prefix + (k,))
