"""The executor's host spans, named after the HMPP directives it performs.

A small 3mm-shaped plan runs under ``jax.profiler.trace`` on the CPU and
the host plane of the recorded trace is read back: every directive the
plan executes appears as an ``hmpp.*`` span inside the ``hmpp.execute``
span of its call, in program order, with its variable and bytes.
"""
import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import Program, execute, plan

N = 8
LOADS = [("hmpp.advancedload", v) for v in "ABCD"]
TAIL = [("hmpp.synchronize", None), ("hmpp.delegatestore", "G"),
        ("hmpp.release", None)]


def _three_mm(loop=0):
    """E := A.B; F := C.D; G := E.F, all three products in a loop of
    ``loop`` iterations when it is not 0."""
    rng = np.random.default_rng(0)
    p = Program("3mm")
    for name in "ABCD":
        p.bind(name, rng.standard_normal((N, N)).astype(np.float32))

    def body():
        p.offload(lambda xp, A, B: {"E": A @ B}, reads=("A", "B"),
                  writes=("E",), name="mm_E")
        p.offload(lambda xp, C, D: {"F": C @ D}, reads=("C", "D"),
                  writes=("F",), name="mm_F")
        p.offload(lambda xp, E, F: {"G": E @ F}, reads=("E", "F"),
                  writes=("G",), name="mm_G")
    if loop:
        with p.loop(loop):
            body()
    else:
        body()
    p.set_outputs("G")
    return p


def _host_spans(log_dir):
    """(name, start_ns, end_ns, stats) of every ``hmpp.*`` host event."""
    path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                out.extend((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                            dict(ev.stats))
                           for ev in line.events
                           if ev.name.startswith("hmpp."))
    return sorted(out, key=lambda s: (s[1], -s[2]))


CASES = {
    # the interpreter launches each block on its own
    "interpreted": (dict(mode="interpreted"), 0,
                    [("hmpp.callsite", b) for b in ("mm_E", "mm_F", "mm_G")]),
    # one fused segment for the three products
    "compiled": (dict(mode="compiled"), 0,
                 [("hmpp.callsite", "mm_E+mm_F+mm_G")]),
    # one launch_loop for the whole loop
    "compiled_fused_loop": (dict(mode="compiled", fuse_loops=True), 3,
                            [("hmpp.callsite", "mm_E+mm_F+mm_G")]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_directive_spans_in_program_order(case, tmp_path):
    kw, loop, callsites = CASES[case]
    pl = plan(_three_mm(loop))
    with jax.profiler.trace(str(tmp_path)):
        outs, stats = execute(pl, **kw)
    spans = _host_spans(tmp_path)

    (name, lo, hi, st), *inner = spans
    assert name == "hmpp.execute" and st == {"mode": kw["mode"]}
    assert all(lo <= a and b <= hi for _, a, b, _ in inner)
    lower = [("hmpp.lower", None)] if kw["mode"] == "compiled" else []
    got = [(n, st.get("var", st.get("blocks"))) for n, _, _, st in inner]
    assert got == lower + LOADS + callsites + TAIL
    # spans follow one another: none starts before the last one ended
    assert all(b <= a2 for (_, _, b, _), (_, a2, _, _)
               in zip(inner, inner[1:]))

    nbytes = N * N * 4
    moved = [st["bytes"] for n, _, _, st in inner
             if n in ("hmpp.advancedload", "hmpp.delegatestore")]
    assert moved == [nbytes] * 5
    assert sum(moved[:4]) == stats.h2d_bytes and moved[4] == stats.d2h_bytes
    if loop:
        assert stats.fused_launches == 1 and stats.kernel_calls == 3 * loop
    np.testing.assert_allclose(
        outs["G"], pl.program.inputs["A"] @ pl.program.inputs["B"]
        @ (pl.program.inputs["C"] @ pl.program.inputs["D"]), rtol=1e-4)


def test_warm_call_has_no_lower_span(tmp_path):
    pl = plan(_three_mm())
    execute(pl, mode="compiled")
    with jax.profiler.trace(str(tmp_path)):
        execute(pl, mode="compiled")
    names = [s[0] for s in _host_spans(tmp_path)]
    assert "hmpp.lower" not in names
    assert names.count("hmpp.execute") == 1
