"""Plain reference and work counts for Polybench 3mm, from its shapes.

The reference runs the three products in float64 with numpy; it imports
nothing of the program.  The work counts are what the computation needs,
whatever implements it: the operations of the three products, the least
device-memory traffic (every input read once, E and F written and read
once, G written once) and the least host-device traffic (the inputs up,
G down).
"""
import numpy as np


def reference(ds, inputs):
    f64 = {k: np.asarray(v, np.float64) for k, v in inputs.items()}
    return (f64["A"] @ f64["B"]) @ (f64["C"] @ f64["D"])


def work(ds, itemsize=4):
    ni, nj, nk, nl, nm = (ds[k] for k in ("NI", "NJ", "NK", "NL", "NM"))
    inputs = ni * nk + nk * nj + nj * nm + nm * nl
    temps = ni * nj + nj * nl
    out = ni * nl
    return {
        "flops": 2 * (ni * nj * nk + nj * nl * nm + ni * nl * nj),
        "hbm_bytes": itemsize * (inputs + 2 * temps + out),
        "moved_bytes": itemsize * (inputs + out),
    }


def control(ds, inputs):
    """The reference one precision below the configuration's: each product
    in three bfloat16 passes (high and low halves, the low-low term
    dropped) with float32 sums, as ``precision="high"`` computes it on a
    TPU, written out so that it computes the same on any device."""
    import jax
    import jax.numpy as jnp

    def mm(a, b):
        def split(x):
            hi = x.astype(jnp.bfloat16)
            return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)

        (ah, al), (bh, bl) = split(a), split(b)

        def dot(x, y):
            return jnp.matmul(x, y, preferred_element_type=jnp.float32)

        return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))

    f = jax.jit(lambda A, B, C, D: mm(mm(A, B), mm(C, D)))
    return np.asarray(f(*(jnp.asarray(inputs[k], jnp.float32)
                          for k in "ABCD")), np.float64)
