"""The benchmark's work counts against values worked out by hand, and the
traffic generator's fixed multisets."""
import numpy as np

from bench import harness
from bench.drivers import serve_rounds

BENCH = harness.BENCH


def _cfg(name):
    return harness.load_json(BENCH / "configs" / f"{name}.json")


def test_3mm_work():
    ref = harness.load_module(BENCH / "ref" / "3mm.py")
    w = ref.work(_cfg("polybench-xl")["datasets"]["3mm"])
    # 2*(1600*1800*2000 + 1800*2200*2400 + 1600*2200*1800)
    assert w["flops"] == 43_200_000_000
    # up: A 1600x2000, B 2000x1800, C 1800x2400, D 2400x2200; down: G
    # 1600x2200; 4 bytes each
    assert w["moved_bytes"] == 65_600_000 + 14_080_000 == 79_680_000
    # the inputs once, E and F written and read once, G written once
    assert w["hbm_bytes"] == 65_600_000 + 2 * 27_360_000 + 14_080_000


def test_rwkv6_3b_counts():
    ref = harness.load_module(BENCH / "ref" / "rwkv6.py")
    cfg = _cfg("rwkv6-3b")
    # 32 x 40 x 64 x 64 x 4 B of state, plus two bf16 shift rows of 2560
    assert ref.state_bytes_per_slot(cfg) == 32 * (40 * 64 * 64 * 4
                                                  + 2 * 2560 * 2)
    assert ref.state_bytes_per_slot(cfg) == 21_299_200
    # per layer: norms 2d; time mix 4d + w_out d^2 + five (d + 2*32d)
    # + four d^2; channel mix 2d + 2*d*f + d^2
    d, f, v = 2560, 8960, 65536
    layer = (2 * d + 4 * d + d * d + 5 * (d + 64 * d) + 4 * d * d
             + 2 * d + 2 * d * f + d * d)
    assert layer == 86_049_280
    assert ref.param_count(cfg) == 32 * layer + 2 * v * d + d
    assert ref.param_count(cfg) * 2 == 6_178_247_680
    # the embedding is a lookup: every other matrix is multiplied through
    per_tok = 32 * (6 * d * d + 5 * 64 * d + 2 * d * f) + d * v
    assert ref.matmul_params_per_token(cfg) == per_tok == 2_920_284_160
    assert ref.flops_per_token(cfg) == 2 * per_tok + 4 * 32 * d * 64


def test_rounds_have_fixed_sizes():
    traffic = harness.load_json(BENCH / "traffic" / "decode.json")
    counts = serve_rounds.ladder_counts(traffic["output_lens"], 256)
    assert sorted(set(counts)) == [64, 128, 256]
    assert [counts.count(x) for x in (64, 128, 256)] == [128, 77, 51]
    a = serve_rounds.make_round(traffic, 256, 1000, harness.rng_for(1, 2))
    b = serve_rounds.make_round(traffic, 256, 1000,
                                harness.rng_for(2**31 + 5, 2))
    for key in ("gen",):
        assert sorted(r[key] for r in a) == sorted(r[key] for r in b)
    assert (sorted(len(r["prompt"]) for r in a)
            == sorted(len(r["prompt"]) for r in b))
    assert [r["gen"] for r in a] != [r["gen"] for r in b]


def test_seeds_past_32_bits():
    a = harness.rng_for(2**40 + 3).integers(0, 1 << 30, 4)
    b = harness.rng_for(3).integers(0, 1 << 30, 4)
    assert not np.array_equal(a, b)
    assert 0 <= harness.jax_seed(2**33) < 2**31
