"""Continuous-batching serving engine: request-level plan executor.

The engine runs ONE device-resident decode batch of fixed capacity and
streams requests through it:

    arrivals ─▶ AdmissionQueue ─▶ prefill (stream 1, shape-bucketed)
                                      │ insert row (donated scatter)
                                      ▼
                   ┌──────── decode batch (capacity C) ────────┐
                   │  every step: ONE jitted decode over all C │
                   │  rows; finished rows retire at boundaries │
                   └───────────────┬───────────────────────────┘
                                   ▼
                  lazy batched token download ─▶ slot recycled

Residency follows the paper end to end: weights are uploaded once
through ``DeviceResidency`` and never move again (noupdate); admission
uploads only the request's prompt (advancedload — the single bulk input
it owns); the decode loop carries tokens/positions/output buffer ON
DEVICE, so steady-state host↔device traffic is zero; generated tokens
come back in one batched fetch per retirement flush (delegatestore).

Shape buckets: prompts are right-padded to power-of-two buckets, so
repeated traffic reuses a handful of compiled prefill shapes, except where
the model's stack carries recurrent state (``EXACT_PROMPTS``), which
padding would corrupt: there each prompt runs at its exact length.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import numpy as np

from .batcher import ContinuousBatcher
from .kvpool import KVSlotPool, cache_bytes_per_slot
from .queue import AdmissionQueue
from .request import Request, RequestState

__all__ = ["ServeRuntime", "Engine", "derive_capacity", "bucket_len"]


def bucket_len(prompt_len: int, max_seq: int, *, exact: bool) -> int:
    """Padded prompt length for a shape bucket: next power of two (min 8),
    capped at ``max_seq``.  ``exact`` stacks (recurrent state) get the
    true length — padding would pollute the carried state."""
    if exact:
        return prompt_len
    return min(max(8, 1 << (prompt_len - 1).bit_length()), max_seq)


def derive_capacity(model, max_seq: int, device_bytes: int,
                    weights_bytes: int) -> int:
    """Decode-batch capacity from a device-bytes budget: whatever is left
    after resident weights, divided by one slot's cache footprint."""
    per_slot = cache_bytes_per_slot(model, max_seq)
    return max(1, (device_bytes - weights_bytes) // max(per_slot, 1))


class ServeRuntime:
    """Compiled machinery shared by engines (and by benchmark modes, so
    continuous-vs-static comparisons never pay a recompile): resident
    params, the bucketed prefill jit, the whole-batch decode jit and the
    admission row-write jit."""

    def __init__(self, cfg, *, max_seq: int, backend: Any = None,
                 params: Any = None, seed: int = 0, use_pallas: bool = False):
        import jax
        import jax.numpy as jnp

        from repro.core.backend import get_backend
        from repro.core.residency import DeviceResidency
        from repro.models import Transformer

        self.cfg = cfg
        self.max_seq = int(max_seq)
        be = get_backend(backend)
        # two logical streams: 0 = decode compute, 1 = prefill + fetches
        self.be = be.variant(n_streams=max(be.n_streams, 2))
        self.model = Transformer(cfg, use_pallas=use_pallas)
        self.exact_buckets = self.model.stack.EXACT_PROMPTS

        # weights resident once, through the instrumented residency layer
        owned = params is None
        if owned:
            # one compiled init: no fp32 temporaries of whole stacked
            # leaves beside the weights already made
            params = jax.jit(self.model.init)(jax.random.key(seed))
        self.residency = DeviceResidency(backend=self.be)
        leaves, treedef = jax.tree.flatten(params)
        del params
        for i, leaf in enumerate(leaves):
            self.residency.put_host(f"w{i:04d}", np.asarray(leaf))
            if owned:
                # drop the init copy at once: the device never holds two
                # weight sets (rwkv6-3b: 6.1 GB each on a 16 GB chip)
                leaf.delete()
        n_leaves = len(leaves)
        del leaves
        for i in range(n_leaves):
            self.residency.prefetch(f"w{i:04d}")   # advancedload, async
        self.params = jax.tree.unflatten(
            treedef, [self.residency.device_value(f"w{i:04d}")
                      for i in range(n_leaves)])
        self.weights_bytes = self.residency.stats.h2d_bytes

        self._prefill = jax.jit(self._prefill_impl)
        self._decode = jax.jit(self._decode_impl,
                               donate_argnums=(1, 2, 3, 4, 5))
        self._admit = jax.jit(self._admit_impl, donate_argnums=(1, 2, 3, 4))
        # park a finished row's tokens device-side so its slot can be
        # reused WITHOUT a host sync; everything downloads in one batch
        self._park = jax.jit(self._park_impl, donate_argnums=(0,))
        self._jnp = jnp

    # -- jitted bodies (each jit is named after its method) ------------------
    def _prefill_impl(self, params, batch, last_pos):
        return self.model.prefill(params, batch, max_seq=self.max_seq,
                                  last_pos=last_pos)

    def _decode_impl(self, params, cache, tok, pos, out_buf, gen_idx):
        """One step for the WHOLE padded batch.  Inactive rows are stepped
        too (their writes land past their read window or are dropped at
        gen_idx == gen_cap); their cache rows are dead until the donated
        insert overwrites them at the next admission."""
        jnp = self._jnp
        C, gen_cap = out_buf.shape
        if self.cfg.input_embeds:
            step_in = {"embeds": jnp.zeros((C, self.cfg.d_model),
                                           jnp.float32)}
        else:
            step_in = {"tokens": tok}
        logits, cache = self.model.decode_step(params, cache, step_in, pos)
        if self.cfg.n_codebooks:
            logits = logits[..., 0, :]
        ntok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out_buf = out_buf.at[jnp.arange(C), gen_idx].set(ntok, mode="drop")
        gen_idx = jnp.where(gen_idx < gen_cap, gen_idx + 1, gen_idx)
        return ntok, pos + 1, out_buf, gen_idx, cache

    @staticmethod
    def _park_impl(park, out, slot, idx):
        return park.at[idx].set(out[slot])

    def _admit_impl(self, logits, tok, pos, out_buf, gen_idx, slot, p0):
        """Write one admitted row's metadata: first sampled token (argmax
        of the prefill's real-last-token logits, computed device-side — no
        host sync at admission), next decode position, output cursor."""
        jnp = self._jnp
        lg = logits[0]
        if self.cfg.n_codebooks:
            lg = lg[0]
        t0 = jnp.argmax(lg).astype(jnp.int32)
        tok = tok.at[slot].set(t0)
        pos = pos.at[slot].set(p0)
        out_buf = out_buf.at[slot, 0].set(t0)
        gen_idx = gen_idx.at[slot].set(1)
        return tok, pos, out_buf, gen_idx

    # -- bucketed prefill ----------------------------------------------------
    def bucket_of(self, prompt_len: int) -> int:
        return bucket_len(prompt_len, self.max_seq,
                          exact=self.exact_buckets)

    def prefill_request(self, req: Request):
        """Pad to the request's bucket, run the prefill on logical stream 1,
        and return (last-real-token logits, cache tree), asynchronously."""
        jnp = self._jnp
        cfg, L = self.cfg, req.prompt_len
        padded = self.bucket_of(L)
        if cfg.input_embeds:
            buf = np.zeros((1, padded, cfg.d_model), np.float32)
            buf[0, :L] = req.prompt
            batch = {"embeds": jnp.asarray(buf)}
        else:
            buf = np.zeros((1, padded), np.int32)
            buf[0, :L] = req.prompt
            batch = {"tokens": jnp.asarray(buf)}
        last_pos = jnp.asarray([L - 1], jnp.int32)
        logits, cache = self._prefill(self.params, batch, last_pos)
        return self.be.track(logits, stream=1), cache


class Engine:
    """The driver loop: admission, continuous decode, lazy retirement."""

    def __init__(self, runtime: ServeRuntime, *, capacity: int,
                 join_policy: str = "continuous", policy: str = "fcfs",
                 max_batch_tokens: Optional[int] = None):
        self.rt = runtime
        self.capacity = int(capacity)
        if max_batch_tokens is None:
            max_batch_tokens = self.capacity * runtime.max_seq
        self.pool = KVSlotPool(runtime.model, self.capacity, runtime.max_seq)
        self.queue = AdmissionQueue(policy, max_batch_tokens)
        self.batcher = ContinuousBatcher(join_policy)
        self.completed: List[Request] = []
        self.fetch_batches = 0

    # -- internals -----------------------------------------------------------
    def _admit_one(self, req: Request, now: float) -> None:
        req.to_prefilling(now)
        slot = self.pool.alloc()
        assert slot is not None   # pop_admissible was bounded by free_count
        logits, cache = self.rt.prefill_request(req)
        self.pool.insert(cache, 0, slot)
        self._tok, self._pos, self._out, self._gidx = self.rt._admit(
            logits, self._tok, self._pos, self._out, self._gidx,
            slot, req.prompt_len)
        req.to_decoding(slot, now)
        self.batcher.join(req, slot)

    def _finish(self, slot: int, now: float) -> None:
        """Retire a row at a step boundary: copy its tokens into the park
        buffer DEVICE-SIDE (async, no sync) and recycle the slot at once —
        the host never waits on a finished request mid-run."""
        req = self.batcher.leave(slot)
        req.to_finished(now)
        idx = self._n_fetched + len(self._parked)
        self._park_buf = self.rt._park(self._park_buf, self._out, slot, idx)
        self._parked.append(req)
        self.pool.free(slot)

    def _flush_retired(self) -> None:
        """delegatestore: ONE download covers every request finished since
        the last flush."""
        if not self._parked:
            return
        buf = self.rt.be.download(self._park_buf, stream=1)
        self.fetch_batches += 1
        for idx, req in enumerate(self._parked, start=self._n_fetched):
            req.retire(np.asarray(buf[idx, :req.max_new_tokens]))
            self.completed.append(req)
        self._n_fetched += len(self._parked)
        self._parked = []

    # -- driver --------------------------------------------------------------
    def run(self, requests: List[Request], *,
            respect_arrivals: bool = True) -> Dict[str, Any]:
        import jax.numpy as jnp
        rt, cfg = self.rt, self.rt.cfg
        for r in requests:
            want = 2 if cfg.input_embeds else 1
            if r.prompt.ndim != want:
                raise ValueError(
                    f"request {r.rid}: prompt ndim {r.prompt.ndim} for "
                    f"{'embeds' if cfg.input_embeds else 'token'} arch")
            if r.total_tokens > rt.max_seq:
                raise ValueError(
                    f"request {r.rid}: prompt+gen {r.total_tokens} exceeds "
                    f"max_seq {rt.max_seq}")
            if (self.queue.max_batch_tokens > 0
                    and r.total_tokens > self.queue.max_batch_tokens):
                raise ValueError(
                    f"request {r.rid}: {r.total_tokens} tokens can never "
                    f"fit the batch budget {self.queue.max_batch_tokens}")
        if not requests:
            self._parked, self._n_fetched = [], 0
            return self._report(0.0)

        C = self.capacity
        gen_cap = max(r.max_new_tokens for r in requests)
        self._tok = jnp.zeros((C,), jnp.int32)
        self._pos = jnp.zeros((C,), jnp.int32)
        self._out = jnp.zeros((C, gen_cap), jnp.int32)
        # gen_idx == gen_cap ⇒ row inactive: its writes drop out of bounds
        self._gidx = jnp.full((C,), gen_cap, jnp.int32)
        self._park_buf = jnp.zeros((len(requests), gen_cap), jnp.int32)
        self._parked: List[Request] = []
        self._n_fetched = 0

        pending = sorted(requests, key=lambda r: (r.arrival_s, r.rid))
        i, t0 = 0, time.perf_counter()
        while i < len(pending) or len(self.queue) or self.batcher.active:
            now = time.perf_counter() - t0
            while i < len(pending) and (not respect_arrivals
                                        or pending[i].arrival_s <= now):
                self.queue.push(pending[i])
                i += 1

            if (len(self.queue) and self.batcher.can_join()
                    and self.pool.free_count > 0):
                for req in self.queue.pop_admissible(
                        self.pool.free_count, self.batcher.tokens_in_flight):
                    self._admit_one(req, time.perf_counter() - t0)
                now = time.perf_counter() - t0
                for slot in self.batcher.finished_now():   # gen == 1
                    self._finish(slot, now)

            if self.batcher.active:
                (self._tok, self._pos, self._out, self._gidx,
                 self.pool.cache) = rt._decode(
                    rt.params, self.pool.cache, self._tok, self._pos,
                    self._out, self._gidx)
                done = self.batcher.step()
                if done:
                    now = time.perf_counter() - t0
                    for slot in done:
                        self._finish(slot, now)
            elif i < len(pending) and not len(self.queue):
                time.sleep(2e-4)   # idle: next arrival not due yet

        self._flush_retired()   # delegatestore: one download for everything
        wall = time.perf_counter() - t0
        self.pool.assert_no_leaks()
        return self._report(wall)

    def _report(self, wall: float) -> Dict[str, Any]:
        done = self.completed
        assert all(r.state is RequestState.FINISHED for r in done)
        lat = np.array([r.latency_s for r in done]) if done else np.array([])
        ttft = np.array([r.t_first_token - r.arrival_s for r in done
                         if r.t_first_token is not None])
        gen_tokens = sum(r.max_new_tokens for r in done)
        rt = self.rt
        return {
            "n_requests": len(done),
            "dropped": 0,
            "wall_s": wall,
            "requests_per_s": len(done) / max(wall, 1e-9),
            "tokens_per_s": gen_tokens / max(wall, 1e-9),
            "gen_tokens": gen_tokens,
            "latency_p50_s": float(np.percentile(lat, 50)) if len(lat)
            else float("nan"),
            "latency_p99_s": float(np.percentile(lat, 99)) if len(lat)
            else float("nan"),
            "ttft_p50_s": float(np.percentile(ttft, 50)) if len(ttft)
            else float("nan"),
            "steps": self.batcher.steps,
            "occupancy": self.batcher.occupancy(self.capacity),
            "join_policy": self.batcher.join_policy,
            "capacity": self.capacity,
            "fetch_batches": self.fetch_batches,
            "queue": self.queue.stats(),
            "pool": self.pool.stats(),
            "residency": {
                "weights_h2d_bytes": rt.weights_bytes,
                "h2d_transfers": rt.residency.stats.h2d_transfers,
                "elided": rt.residency.stats.elided,
            },
        }
