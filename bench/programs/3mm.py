"""Polybench 3mm as an offload program: E := A.B; F := C.D; G := E.F.

The benchmark's own copy of `repro.polybench`'s 3mm program, built through
the public ``Program`` API at the dataset's rectangular sizes.  ``G``, the
full result, is a program output: the host consumer downloads it anyway,
so comparing every element adds no transfer.
"""
import numpy as np

OUTPUT = "G"


def make_inputs(ds, rng, dtype=np.float32):
    ni, nj, nk, nl, nm = (ds[k] for k in ("NI", "NJ", "NK", "NL", "NM"))
    shapes = {"A": (ni, nk), "B": (nk, nj), "C": (nj, nm), "D": (nm, nl)}
    return {k: rng.standard_normal(s, np.float32).astype(dtype)
            for k, s in shapes.items()}


def build(ds, inputs):
    from repro.core import Program
    p = Program("3mm")
    for name, value in inputs.items():
        p.bind(name, value)
    p.offload(lambda xp, A, B: {"E": A @ B}, reads=("A", "B"),
              writes=("E",), name="mm_E")
    p.offload(lambda xp, C, D: {"F": C @ D}, reads=("C", "D"),
              writes=("F",), name="mm_F")
    p.offload(lambda xp, E, F: {"G": E @ F}, reads=("E", "F"),
              writes=("G",), name="mm_G")
    p.host(lambda xp, G: {"out": G.sum(axis=0, keepdims=True)},
           reads=("G",), writes=("out",), name="consume")
    p.set_outputs("G", "out")
    return p
