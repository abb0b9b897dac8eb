"""ISSUE 8: continuous-batching serving engine.

Covers the serve package bottom-up — request lifecycle legality, the
admission queue's policies and token budget, KV-slot pool churn /
bit-reuse / leak detection — then the engine end to end: token-exact
equality against a per-request reference decode (padded buckets on a
dense arch, exact buckets on rwkv), the gen=1 degenerate case, the
static-join baseline, over-capacity queueing and donation defaults.
"""
import math

import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.serve import (AdmissionQueue, ContinuousBatcher, Engine,
                         KVSlotPool, Request, RequestState, ServeRuntime,
                         bucket_len, cache_bytes_per_slot, make_trace)

MAX_SEQ = 48


def _tokens(L, seed=0):
    return np.random.default_rng(seed).integers(0, 257, (L,)).astype(np.int32)


def _req(rid, L=8, gen=4, arrival=0.0, seed=None):
    return Request(rid=rid, prompt=_tokens(L, seed if seed is not None
                                           else rid),
                   max_new_tokens=gen, arrival_s=arrival)


# ---------------------------------------------------------------------------
# Request lifecycle
# ---------------------------------------------------------------------------

class TestRequestLifecycle:
    def test_legal_path(self):
        r = _req(0, gen=2)
        assert r.state is RequestState.QUEUED
        r.to_prefilling(0.1)
        r.to_decoding(slot=3, now=0.2)
        r.to_finished(0.5)
        r.retire(np.zeros((2,), np.int32))
        assert r.slot == 3 and r.latency_s == pytest.approx(0.5)

    def test_illegal_transitions_raise(self):
        r = _req(0)
        with pytest.raises(RuntimeError, match="illegal transition"):
            r.to_decoding(slot=0, now=0.0)       # must prefill first
        r.to_prefilling(0.0)
        with pytest.raises(RuntimeError, match="illegal transition"):
            r.to_finished(0.0)                   # must decode first

    def test_validation(self):
        with pytest.raises(ValueError, match="max_new_tokens"):
            _req(0, gen=0)
        with pytest.raises(ValueError, match="prompt"):
            Request(rid=0, prompt=np.zeros((0,), np.int32), max_new_tokens=1)

    def test_total_tokens(self):
        assert _req(0, L=8, gen=4).total_tokens == 12


# ---------------------------------------------------------------------------
# Admission queue
# ---------------------------------------------------------------------------

class TestAdmissionQueue:
    def test_fcfs_order(self):
        q = AdmissionQueue("fcfs")
        for rid, L in enumerate((24, 8, 16)):
            q.push(_req(rid, L=L))
        got = q.pop_admissible(3, 0)
        assert [r.rid for r in got] == [0, 1, 2]

    def test_sjf_prefers_short_prompts(self):
        q = AdmissionQueue("sjf")
        for rid, L in enumerate((24, 8, 16)):
            q.push(_req(rid, L=L))
        got = q.pop_admissible(2, 0)
        assert [r.prompt_len for r in got] == [8, 16]
        assert len(q) == 1                       # long one waits, not dropped

    def test_budget_blocks_in_order(self):
        q = AdmissionQueue("fcfs", max_batch_tokens=30)
        q.push(_req(0, L=8, gen=4))   # 12
        q.push(_req(1, L=20, gen=4))  # 24: 12+24 > 30 -> blocks
        q.push(_req(2, L=8, gen=4))   # behind the blocked one: waits too
        got = q.pop_admissible(3, 0)
        assert [r.rid for r in got] == [0]
        assert len(q) == 2
        s = q.stats()
        assert s["arrived"] == 3 and s["peak_depth"] == 3

    def test_slot_bound(self):
        q = AdmissionQueue("fcfs")
        for rid in range(4):
            q.push(_req(rid))
        assert len(q.pop_admissible(2, 0)) == 2

    def test_unknown_policy(self):
        with pytest.raises(ValueError, match="policy"):
            AdmissionQueue("priority")


# ---------------------------------------------------------------------------
# KV-slot pool
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def rwkv_cfg():
    return reduced(get_config("rwkv6-3b"))


@pytest.fixture(scope="module")
def dense_cfg():
    return reduced(get_config("qwen2.5-14b"))


class TestKVSlotPool:
    def test_churn_never_exceeds_capacity(self, rwkv_cfg):
        from repro.models import Transformer
        pool = KVSlotPool(Transformer(rwkv_cfg), capacity=3, max_seq=16)
        held = []
        for i in range(50):
            s = pool.alloc()
            if s is None:
                assert pool.in_use == 3
                pool.free(held.pop(0))
            else:
                held.append(s)
            assert pool.in_use <= 3
        for s in held:
            pool.free(s)
        pool.assert_no_leaks()
        assert pool.stats()["peak_in_use"] == 3
        assert pool.stats()["reused_slots"] > 0   # churn recycled indices

    def test_lifo_bit_reuse(self, rwkv_cfg):
        from repro.models import Transformer
        pool = KVSlotPool(Transformer(rwkv_cfg), capacity=4, max_seq=16)
        a, b = pool.alloc(), pool.alloc()
        pool.free(b)
        assert pool.alloc() == b     # the just-freed slot comes back first
        pool.free(a)
        assert pool.alloc() == a

    def test_double_free_raises(self, rwkv_cfg):
        from repro.models import Transformer
        pool = KVSlotPool(Transformer(rwkv_cfg), capacity=2, max_seq=16)
        s = pool.alloc()
        pool.free(s)
        with pytest.raises(RuntimeError, match="double free"):
            pool.free(s)

    def test_leak_detection(self, rwkv_cfg):
        from repro.models import Transformer
        pool = KVSlotPool(Transformer(rwkv_cfg), capacity=2, max_seq=16)
        pool.alloc()
        with pytest.raises(RuntimeError, match="leak"):
            pool.assert_no_leaks()

    def test_insert_requires_allocated_slot(self, rwkv_cfg):
        from repro.models import Transformer
        m = Transformer(rwkv_cfg)
        pool = KVSlotPool(m, capacity=2, max_seq=16)
        with pytest.raises(RuntimeError, match="unallocated"):
            pool.insert(m.init_cache(1, 16), 0, 0)

    def test_batch_axis_inference_griffin(self):
        """Griffin's cache mixes (periods, 2, B, ...) recurrent leaves
        with (periods, B, W, ...) ring-buffer leaves — the inferred axis
        must differ per leaf, not be assumed constant."""
        from repro.models import Transformer
        cfg = reduced(get_config("recurrentgemma-2b"))
        pool = KVSlotPool(Transformer(cfg), capacity=2, max_seq=16)
        assert len(set(pool.batch_axes)) > 1

    def test_bytes_per_slot_positive(self, rwkv_cfg):
        from repro.models import Transformer
        assert cache_bytes_per_slot(Transformer(rwkv_cfg), 16) > 0


# ---------------------------------------------------------------------------
# Batcher
# ---------------------------------------------------------------------------

class TestBatcher:
    def test_continuous_joins_any_time(self):
        b = ContinuousBatcher("continuous")
        b.join(_req(0, gen=3), 0)
        assert b.can_join()

    def test_static_joins_only_when_empty(self):
        b = ContinuousBatcher("static")
        assert b.can_join()
        b.join(_req(0, gen=3), 0)
        assert not b.can_join()
        b.step(); b.step()
        assert b.leave(0).rid == 0
        assert b.can_join()

    def test_step_counts_down(self):
        b = ContinuousBatcher()
        b.join(_req(0, gen=3), 0)
        b.join(_req(1, gen=1), 1)
        assert b.finished_now() == [1]           # gen=1: done pre-decode
        b.leave(1)
        assert b.step() == [] and b.step() == [0]


# ---------------------------------------------------------------------------
# Shape buckets
# ---------------------------------------------------------------------------

def test_bucket_len():
    assert bucket_len(3, 64, exact=False) == 8       # floor
    assert bucket_len(9, 64, exact=False) == 16      # next pow2
    assert bucket_len(16, 64, exact=False) == 16     # exact pow2 kept
    assert bucket_len(60, 64, exact=False) == 64     # capped at max_seq
    assert bucket_len(13, 64, exact=True) == 13      # recurrent: exact


# ---------------------------------------------------------------------------
# Engine end-to-end
# ---------------------------------------------------------------------------

def _reference_decode(rt, req):
    """Per-request greedy decode straight through the model — the
    launch.serve loop at batch=1, no padding, no pooling."""
    import jax
    import jax.numpy as jnp
    cfg, model, params = rt.cfg, rt.model, rt.params
    if cfg.input_embeds:
        batch = {"embeds": jnp.asarray(req.prompt[None])}
    else:
        batch = {"tokens": jnp.asarray(req.prompt[None])}
    logits, cache = model.prefill(params, batch, max_seq=rt.max_seq)
    decode = jax.jit(model.decode_step)
    if cfg.n_codebooks:
        logits = logits[..., 0, :]
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    out = [int(tok[0])]
    for i in range(req.max_new_tokens - 1):
        pos = jnp.full((1,), req.prompt_len + i, jnp.int32)
        step = ({"embeds": jnp.zeros((1, cfg.d_model), jnp.float32)}
                if cfg.input_embeds else {"tokens": tok})
        logits, cache = decode(params, cache, step, pos)
        if cfg.n_codebooks:
            logits = logits[..., 0, :]
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out.append(int(tok[0]))
    return np.array(out, np.int32)


@pytest.fixture(scope="module")
def rwkv_rt(rwkv_cfg):
    return ServeRuntime(rwkv_cfg, max_seq=MAX_SEQ, seed=0)


@pytest.fixture(scope="module")
def dense_rt(dense_cfg):
    return ServeRuntime(dense_cfg, max_seq=MAX_SEQ, seed=0)


def _mixed_trace(rids, lens, gens):
    return [_req(r, L=L, gen=g) for r, (L, g) in
            zip(rids, zip(lens, gens))]


class TestEngineTokens:
    """Continuous batching must be a pure scheduling change: every
    request's tokens equal its standalone greedy decode."""

    def test_dense_padded_buckets_exact(self, dense_rt):
        # lengths straddle two pow2 buckets (8 and 16); interleaved joins
        reqs = _mixed_trace(range(6), (5, 8, 11, 16, 7, 9),
                            (4, 6, 2, 5, 3, 6))
        eng = Engine(dense_rt, capacity=3)
        eng.run(reqs, respect_arrivals=False)
        assert len(eng.completed) == 6
        for r in eng.completed:
            np.testing.assert_array_equal(
                r.tokens, _reference_decode(dense_rt, r),
                err_msg=f"rid={r.rid} L={r.prompt_len}")

    def test_rwkv_exact_buckets_exact(self, rwkv_rt):
        reqs = _mixed_trace(range(5), (6, 9, 12, 6, 9), (4, 5, 2, 6, 3))
        eng = Engine(rwkv_rt, capacity=2)
        eng.run(reqs, respect_arrivals=False)
        for r in eng.completed:
            np.testing.assert_array_equal(
                r.tokens, _reference_decode(rwkv_rt, r),
                err_msg=f"rid={r.rid} L={r.prompt_len}")

    def test_gen1_finishes_without_decoding(self, rwkv_rt):
        reqs = [_req(0, L=8, gen=1), _req(1, L=8, gen=3)]
        eng = Engine(rwkv_rt, capacity=2)
        rep = eng.run(reqs, respect_arrivals=False)
        assert rep["n_requests"] == 2
        r0 = next(r for r in eng.completed if r.rid == 0)
        np.testing.assert_array_equal(
            r0.tokens, _reference_decode(rwkv_rt, r0)[:1])


class TestEngineScheduling:
    def test_over_capacity_queues_not_ooms(self, rwkv_rt):
        reqs = [_req(i, L=8, gen=3) for i in range(7)]
        eng = Engine(rwkv_rt, capacity=2)
        rep = eng.run(reqs, respect_arrivals=False)
        assert rep["n_requests"] == 7 and rep["dropped"] == 0
        assert rep["pool"]["peak_in_use"] <= 2
        assert rep["queue"]["peak_depth"] >= 5   # the rest waited in queue
        assert rep["pool"]["reused_slots"] >= 5  # slot indices recycled

    def test_static_mode_takes_more_steps(self, rwkv_rt):
        # one long request per pair: static drains to the long tail
        reqs = [_req(i, L=8, gen=(12 if i % 2 else 2)) for i in range(6)]
        cont = Engine(rwkv_rt, capacity=2, join_policy="continuous")
        c = cont.run([_req(r.rid, L=r.prompt_len, gen=r.max_new_tokens)
                      for r in reqs], respect_arrivals=False)
        stat = Engine(rwkv_rt, capacity=2, join_policy="static")
        s = stat.run(reqs, respect_arrivals=False)
        assert s["n_requests"] == c["n_requests"] == 6
        assert s["steps"] > c["steps"]
        assert c["occupancy"] > s["occupancy"]

    def test_token_budget_respected(self, rwkv_rt):
        reqs = [_req(i, L=8, gen=4) for i in range(4)]      # 12 tokens each
        eng = Engine(rwkv_rt, capacity=4, max_batch_tokens=25)  # fits 2
        rep = eng.run(reqs, respect_arrivals=False)
        assert rep["n_requests"] == 4
        assert rep["pool"]["peak_in_use"] <= 2

    def test_oversized_request_rejected(self, rwkv_rt):
        eng = Engine(rwkv_rt, capacity=2)
        with pytest.raises(ValueError, match="max_seq"):
            eng.run([_req(0, L=MAX_SEQ, gen=8)])

    def test_p99_and_throughput_reported(self, rwkv_rt):
        eng = Engine(rwkv_rt, capacity=2)
        rep = eng.run([_req(i, gen=2) for i in range(3)],
                      respect_arrivals=False)
        assert math.isfinite(rep["latency_p99_s"])
        assert rep["requests_per_s"] > 0 and rep["tokens_per_s"] > 0
        assert rep["fetch_batches"] >= 1   # delegatestore: batched fetches

    def test_respects_arrival_times(self, rwkv_rt):
        reqs = [_req(0, gen=2, arrival=0.0), _req(1, gen=2, arrival=0.05)]
        eng = Engine(rwkv_rt, capacity=2)
        eng.run(reqs, respect_arrivals=True)
        r1 = next(r for r in eng.completed if r.rid == 1)
        assert r1.t_admit >= 0.05          # not admitted before it arrived


# ---------------------------------------------------------------------------
# Donation (satellite a + c)
# ---------------------------------------------------------------------------

def _donation_supported():
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x + 1.0, donate_argnums=0)
    x = jnp.ones((4,), jnp.float32)
    f(x)
    return x.is_deleted()


class TestDonationDefault:
    def test_jax_backend_donates_by_default(self):
        from repro.core.backend import (JaxDeviceBackend, PinnedHostBackend,
                                        get_backend)
        assert JaxDeviceBackend().donate
        assert PinnedHostBackend().donate
        assert get_backend("jax").donate
        assert not JaxDeviceBackend(donate=False).donate  # explicit opt-out

    def test_pool_insert_donates_buffers(self, rwkv_rt):
        """Slot recycling reuses device memory: the donated insert must
        consume the previous pooled buffers."""
        if not _donation_supported():
            pytest.skip("platform does not implement buffer donation")
        import jax
        pool = KVSlotPool(rwkv_rt.model, capacity=2, max_seq=MAX_SEQ)
        slot = pool.alloc()
        before = jax.tree.leaves(pool.cache)
        _, cache = rwkv_rt.prefill_request(_req(0, L=8, gen=2))
        pool.insert(cache, 0, slot)
        assert all(leaf.is_deleted() for leaf in before)
        pool.free(slot)
        pool.assert_no_leaks()


# ---------------------------------------------------------------------------
# launch.serve (satellite b) + load generator
# ---------------------------------------------------------------------------

class TestServeOneShot:
    def test_gen1_reports_sane_metrics(self, rwkv_cfg):
        from repro.launch.serve import serve
        out = serve(rwkv_cfg, batch=2, prompt_len=4, gen=1)
        assert out["generated"].shape == (2, 1)
        assert out["decode_tok_s"] == 0.0          # no decode loop ran
        assert math.isfinite(out["tokens_per_s"])
        # end-to-end rate is bounded by actual elapsed time
        total = out["prefill_s"] + out["decode_s"]
        assert out["tokens_per_s"] == pytest.approx(2 / total, rel=1e-6)

    def test_gen2_decode_rate_positive(self, rwkv_cfg):
        from repro.launch.serve import serve
        out = serve(rwkv_cfg, batch=2, prompt_len=4, gen=2)
        assert out["generated"].shape == (2, 2)
        assert out["decode_tok_s"] > 0.0


class TestLoadGenerator:
    def test_seeded_and_sorted(self, rwkv_cfg):
        a = make_trace(rwkv_cfg, n_requests=10, rate_rps=100.0, seed=7)
        b = make_trace(rwkv_cfg, n_requests=10, rate_rps=100.0, seed=7)
        for x, y in zip(a, b):
            assert x.arrival_s == y.arrival_s
            np.testing.assert_array_equal(x.prompt, y.prompt)
        assert all(a[i].arrival_s <= a[i + 1].arrival_s
                   for i in range(len(a) - 1))

    def test_max_seq_clamp(self, rwkv_cfg):
        t = make_trace(rwkv_cfg, n_requests=40, rate_rps=1e6, seed=0,
                       max_seq=16)
        assert all(r.total_tokens <= 16 for r in t)

    def test_embeds_arch_prompts(self):
        cfg = reduced(get_config("chameleon-34b"))
        if not cfg.input_embeds:
            pytest.skip("arch does not use input embeds")
        t = make_trace(cfg, n_requests=3, rate_rps=1e6, seed=0)
        assert all(r.prompt.ndim == 2 and r.prompt.shape[1] == cfg.d_model
                   for r in t)


class TestServeBenchSmoke:
    def test_quick_bench_invariants(self, tmp_path):
        """The CI smoke: tiny trace, both modes finish everything, p99
        finite, zero leaks (no speedup gate —
        scheduling wins need a bigger trace than a unit test should pay
        for)."""
        import sys
        sys.path.insert(0, "benchmarks")
        try:
            import serve_bench
        finally:
            sys.path.pop(0)
        row = serve_bench.bench(arch="rwkv6-3b", n_requests=8, capacity=2,
                                max_seq=32, seed=0, gate=False)
        assert row["pool"]["in_use"] == 0
        assert math.isfinite(row["continuous"]["latency_p99_s"])
        assert math.isfinite(row["static"]["latency_p99_s"])
