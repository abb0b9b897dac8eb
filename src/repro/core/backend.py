"""Pluggable execution backends for the plan runtime.

The paper's generated HMPP code targets a CPU+GPU pair with asynchronous
queues; our executor used to hard-code "host = numpy, device = default JAX
device, every transfer blocks".  This module factors that choice out into a
``Backend`` protocol — alloc/upload/download/launch/sync plus per-stream
events — so the same ``Plan`` can run against:

``NumpyHostBackend``
    Both spaces are numpy.  Transfers are copies, launches run the block
    body with ``numpy``.  Useful for validating plans (the residency
    discipline is still enforced by the driver) without touching JAX.

``JaxDeviceBackend``
    Device space is the default JAX device.  ``upload`` is an async
    ``jax.device_put`` enqueued on one of ``n_streams`` logical transfer
    streams (double-buffered by default), launches are jitted and dispatch
    asynchronously, and ``sync(stream)`` is a *real* wait point: it blocks
    on every event outstanding on that stream.  Buffer donation for
    fused launches is ON by default (the serving engine's decode path
    exercises it every step); construct with ``donate=False`` to opt
    out.

``PinnedHostBackend``
    Same as ``JaxDeviceBackend`` but the host side of every transfer is
    staged in the device's ``pinned_host`` memory, which is what makes
    h2d genuinely overlappable on TPU.  Construction fails on a device
    without a pinned space (``repro.optim.offload.host_memory_kind``).

Streams are logical ids chosen by the planner (``AdvancedLoad.stream``
etc.); a backend may map many logical streams onto fewer physical ones
(``stream % n_streams``).  Stream 0 is the compute stream by convention.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = [
    "Backend", "Event", "NumpyHostBackend", "JaxDeviceBackend",
    "PinnedHostBackend", "get_backend", "register_backend",
]


@dataclasses.dataclass
class Event:
    """Completion handle for an async backend operation.

    ``payload`` is whatever must be ready before the op is complete (a
    jax.Array for device backends, nothing for numpy).  ``wait()`` is
    idempotent.
    """
    payload: Any = None
    _done: bool = False

    def wait(self) -> None:
        if self._done:
            return
        if self.payload is not None and hasattr(self.payload,
                                                "block_until_ready"):
            try:
                self.payload.block_until_ready()
            except RuntimeError:
                pass   # buffer deleted/donated since: nothing left to wait on
        self._done = True


class Backend:
    """Protocol for plan-execution backends (duck-typed; subclass for the
    shared stream bookkeeping).

    Handles returned by ``upload``/``launch`` are opaque to the driver; it
    only stores them in slots and passes them back in.
    """

    name: str = "abstract"
    n_streams: int = 2   # logical transfer streams (double-buffered)
    supports_donation: bool = False   # can ``donate=True`` change execution?

    def __init__(self) -> None:
        self._pending: Dict[int, List[Event]] = {}
        self.loop_dispatches = 0   # fused whole-loop launches (launch_loop)

    def variant(self, *, n_streams: Optional[int] = None,
                donate: Optional[bool] = None) -> "Backend":
        """A backend identical to this one except for the given knobs —
        the tuner uses it to measure each candidate on a PHYSICALLY
        matching backend (a streams-3 plan on a 3-queue backend, a
        donate candidate on a donating one) instead of folding every
        config onto the caller's instance.  Backends without the knob
        return themselves; implementations must memoize twins so jit /
        lowering caches are shared across tuning calls."""
        return self

    @property
    def xp(self):
        """Array namespace block bodies run under (numpy or jax.numpy)."""
        raise NotImplementedError

    # -- stream/event bookkeeping (shared) ---------------------------------
    _MAX_PENDING = 64     # per stream; oldest events are drained past this

    def _stream_of(self, stream: int) -> int:
        """Logical → physical stream.  Stream 0 (compute) is reserved;
        transfer streams 1..∞ fold onto the backend's 1..n_streams so
        they never collide with the compute queue."""
        if stream <= 0:
            return 0
        return 1 + (stream - 1) % max(self.n_streams, 1)

    def _record(self, stream: int, ev: Event) -> Event:
        q = self._pending.setdefault(self._stream_of(stream), [])
        q.append(ev)
        # bound the queue so callers that never sync (e.g. a residency
        # prefetch loop) don't pin every in-flight array forever
        while len(q) > self._MAX_PENDING:
            q.pop(0).wait()
        return ev

    def sync(self, stream: Optional[int] = None) -> None:
        """Block until every event on ``stream`` (or all streams) is done."""
        keys = (list(self._pending) if stream is None
                else [self._stream_of(stream)])
        for k in keys:
            for ev in self._pending.pop(k, ()):
                ev.wait()

    def track(self, handle: Any, *, stream: int = 0) -> Any:
        """Register an externally produced handle (e.g. a fused-launch
        output) so a later ``sync(stream)`` waits on it."""
        self._record(stream, Event(payload=handle))
        return handle

    # -- memory ------------------------------------------------------------
    def alloc(self, shape: Tuple[int, ...], dtype) -> Any:
        """Fresh zero device buffer (used for pruned/dead block inputs)."""
        raise NotImplementedError

    def upload(self, host: np.ndarray, *, stream: int = 0,
               name: Optional[str] = None) -> Any:
        """h2d: returns a device handle; completion tracked on ``stream``.

        ``name`` is the plan variable being uploaded — mesh/sharded
        backends key per-variable placements on it (``MeshBackend``
        shards or replicates by name); single-device backends ignore
        it."""
        raise NotImplementedError

    def download(self, handle: Any, *, stream: int = 0) -> np.ndarray:
        """d2h: returns a host ndarray (a wait point for ``handle``)."""
        raise NotImplementedError

    def free(self, handle: Any) -> None:
        """Release a device handle (HMPP ``release``).  Events waiting on
        the handle are retired first so a later ``sync`` never blocks on
        a deleted buffer."""
        for q in self._pending.values():
            for ev in q:
                if ev.payload is handle:
                    ev.payload, ev._done = None, True

    # -- compute -----------------------------------------------------------
    def launch(self, fn: Callable[..., Dict[str, Any]],
               names: Sequence[str], writes: Sequence[str],
               args: Sequence[Any], *, stream: int = 0) -> Tuple[Any, ...]:
        """Run one offload block body; returns device handles for
        ``writes`` in order.  Dispatch may be asynchronous."""
        raise NotImplementedError

    def compile_fused(self, fused_fn: Callable[..., Tuple[Any, ...]],
                      donate_argnums: Tuple[int, ...] = ()
                      ) -> Callable[..., Tuple[Any, ...]]:
        """Lower a fused segment function (see ``core.compile``) to this
        backend's compiled form.  ``donate_argnums`` marks inputs the
        caller will not reuse; backends may ignore it.  Default: eager."""
        return fused_fn

    def launch_loop(self, body_fn: Callable[[Dict[str, Any]],
                                            Dict[str, Any]],
                    n_iters: int, carry: Dict[str, Any],
                    *, stream: int = 0,
                    donate_keys: Sequence[str] = ()) -> Dict[str, Any]:
        """Whole-loop launch: run ``carry = body_fn(carry)`` ``n_iters``
        times as ONE backend dispatch and return the final carry.

        ``carry`` maps loop-state names to device handles; ``body_fn`` is
        pure (built by ``core.compile`` over ``self.xp``) and returns a
        carry with the same keys plus any body-defined variables, whose
        values stabilize in shape/dtype after the first iteration.  Device
        backends lower this to a single jitted ``lax.fori_loop``
        (body-defined state is zero-initialized from ``jax.eval_shape`` —
        NOT peeled: a peeled iteration compiles in a different XLA context
        than the while body and breaks bitwise parity); the numpy backend
        runs a Python loop inside the one dispatch, keeping the contract
        backend-uniform.  ``loop_dispatches`` counts calls.

        ``donate_keys`` names carry entries whose pre-launch buffers the
        caller will not reuse (rewritten loop state — the fused-loop
        analogue of segment arg donation); backends may donate them to
        the launch.  Opt-in: only backends constructed with
        ``donate=True`` act on it.
        """
        if n_iters < 1:
            raise ValueError("launch_loop needs n_iters >= 1")
        self.loop_dispatches += 1
        return self._launch_loop(body_fn, n_iters, carry, stream=stream,
                                 donate_keys=tuple(donate_keys))

    def _launch_loop(self, body_fn, n_iters: int, carry: Dict[str, Any],
                     *, stream: int = 0,
                     donate_keys: Tuple[str, ...] = ()) -> Dict[str, Any]:
        raise NotImplementedError

    def loop_in_body(self, body_fn: Callable[[Dict[str, Any]],
                                             Dict[str, Any]],
                     n_iters: int, env: Dict[str, Any]) -> Dict[str, Any]:
        """Run ``env = body_fn(env)`` ``n_iters`` times INSIDE a trace —
        the primitive nested fused loops are built from (the outer loop's
        body is ``loop_in_body`` over the inner one).  Default: a plain
        Python loop (numpy, or any eager backend).  Device backends
        override it with an in-trace ``lax.fori_loop``."""
        for _ in range(n_iters):
            env = body_fn(env)
        return env


class NumpyHostBackend(Backend):
    """Both memory spaces are numpy; the device is simulated with copies so
    residency bugs (reading a stale space) still surface as wrong counts."""

    name = "numpy"

    @property
    def xp(self):
        return np

    def alloc(self, shape, dtype):
        return np.zeros(shape, dtype)

    def upload(self, host, *, stream: int = 0, name=None):
        handle = np.array(host, copy=True)
        self._record(stream, Event(payload=None, _done=True))
        return handle

    def download(self, handle, *, stream: int = 0):
        return np.array(handle, copy=True)

    def launch(self, fn, names, writes, args, *, stream: int = 0):
        out = fn(np, **dict(zip(names, args)))
        self._record(stream, Event(payload=None, _done=True))
        return tuple(np.asarray(out[w]) for w in writes)

    def compile_fused(self, fused_fn, donate_argnums=()):
        return fused_fn            # no tracing: eager numpy

    def _launch_loop(self, body_fn, n_iters, carry, *, stream: int = 0,
                     donate_keys=()):
        for _ in range(n_iters):
            carry = body_fn(carry)
        self._record(stream, Event(payload=None, _done=True))
        return carry


@functools.lru_cache(maxsize=512)
def _jitted_block(fn, names: Tuple[str, ...], writes: Tuple[str, ...]):
    import jax
    import jax.numpy as jnp

    def wrapped(*arrays):
        out = fn(jnp, **dict(zip(names, arrays)))
        return tuple(out[w] for w in writes)
    return jax.jit(wrapped)


class JaxDeviceBackend(Backend):
    """Default JAX device space, async transfers on logical streams."""

    name = "jax"
    supports_donation = True

    # Donation defaults ON (ISSUE 8): the serve decode path donates the
    # pooled KV cache every step, and the tuner always measures donate
    # candidates on an explicit ``variant(donate=...)`` twin, so the
    # default only affects direct ``execute()`` callers — whose inputs
    # are re-uploaded from host per call and never alias a donated
    # buffer.  ``donate=False`` is the explicit opt-out.
    def __init__(self, device=None, *, n_streams: int = 2,
                 donate: bool = True):
        super().__init__()
        import jax
        self._jax = jax
        self._device = device if device is not None else jax.devices()[0]
        self.n_streams = n_streams
        self.donate = donate
        # (n_streams, donate) -> twin; shared by every twin of this
        # device so variant-of-variant returns the original instance
        self._variant_pool: Dict[Tuple[int, bool], "JaxDeviceBackend"] = {
            (n_streams, donate): self}

    def variant(self, *, n_streams: Optional[int] = None,
                donate: Optional[bool] = None) -> "JaxDeviceBackend":
        ns = self.n_streams if n_streams is None else max(1, int(n_streams))
        dn = self.donate if donate is None else bool(donate)
        twin = self._variant_pool.get((ns, dn))
        if twin is None:
            twin = type(self)(device=self._device, n_streams=ns, donate=dn)
            twin._variant_pool = self._variant_pool
            self._variant_pool[(ns, dn)] = twin
        return twin

    @property
    def xp(self):
        import jax.numpy as jnp
        return jnp

    def alloc(self, shape, dtype):
        import jax.numpy as jnp
        return jnp.zeros(shape, dtype)

    def upload(self, host, *, stream: int = 0, name=None):
        handle = self._jax.device_put(host, self._device)   # async dispatch
        self._record(stream, Event(payload=handle))
        return handle

    def download(self, handle, *, stream: int = 0):
        return np.asarray(handle)                           # wait point

    def free(self, handle) -> None:
        super().free(handle)       # retire events waiting on this buffer
        if hasattr(handle, "delete"):
            try:
                handle.delete()
            except Exception:
                pass   # buffer may be donated/shared; dropping the ref wins

    def launch(self, fn, names, writes, args, *, stream: int = 0):
        outs = _jitted_block(fn, tuple(names), tuple(writes))(*args)
        for o in outs:
            self._record(stream, Event(payload=o))
        return outs

    def compile_fused(self, fused_fn, donate_argnums=()):
        if donate_argnums and self.donate:
            return self._jax.jit(fused_fn, donate_argnums=donate_argnums)
        return self._jax.jit(fused_fn)

    def loop_in_body(self, body_fn, n_iters, env):
        """In-trace whole loop — THE single fencing/zero-init discipline
        both the flat `_launch_loop` and nested fusion build on.

        optimization_barrier fences each iteration: without it XLA
        hoists loop-invariant work (CSE/LICM) and re-fuses across
        iterations, which changes FMA rounding and breaks the
        bitwise-equality contract with the per-iteration interpreted/
        segment paths.  Body-defined carry slots (written before any
        read on every valid plan) are discovered abstractly and
        zero-initialized, so EVERY iteration runs inside the fori_loop —
        peeling iteration 0 to top level instead would compile it in a
        different XLA context than the while body and break bitwise
        equality (seen on CPU)."""
        jax = self._jax
        import jax.numpy as jnp

        def one_iter(e):
            e = jax.lax.optimization_barrier(dict(e))
            return jax.lax.optimization_barrier(dict(body_fn(e)))

        shapes = jax.eval_shape(body_fn, env)
        env = dict(env)
        for k, sd in shapes.items():
            if k not in env:
                env[k] = jnp.zeros(sd.shape, sd.dtype)
        return jax.lax.fori_loop(0, n_iters, lambda i, e: one_iter(e), env)

    def _launch_loop(self, body_fn, n_iters, carry, *, stream: int = 0,
                     donate_keys=()):
        # the jitted whole-loop is cached ON body_fn so it lives exactly
        # as long as the compiled plan that owns the closure (a cache on
        # the backend would pin every program forever: the jit references
        # body_fn, so a backend-held mapping entry could never be freed)
        per_fn = getattr(body_fn, "_launch_loop_cache", None)
        if per_fn is None:
            per_fn = body_fn._launch_loop_cache = {}
        dkeys = (tuple(sorted(k for k in donate_keys if k in carry))
                 if self.donate else ())
        jitted = per_fn.get((n_iters, dkeys))
        if jitted is None:
            def whole(donated, kept):
                env = dict(kept)
                env.update(donated)
                return self.loop_in_body(body_fn, n_iters, env)

            # rewritten loop state is donated to the launch (the caller
            # only keeps the final carry), mirroring segment donation
            jitted = self._jax.jit(whole,
                                   donate_argnums=(0,) if dkeys else ())
            per_fn[(n_iters, dkeys)] = jitted
        donated = {k: carry[k] for k in dkeys}
        kept = {k: v for k, v in carry.items() if k not in dkeys}
        out = jitted(donated, kept)
        for v in out.values():
            self._record(stream, Event(payload=v))
        return out


class PinnedHostBackend(JaxDeviceBackend):
    """JAX backend whose transfers stage through ``pinned_host`` memory —
    the ``optim/offload.py`` machinery applied to the block executor.
    Both hops name their memory kind explicitly: a bare-device
    ``device_put`` of a pinned buffer is read by JAX as a same-device
    copy and refused for its memory kind."""

    name = "pinned"

    def __init__(self, device=None, *, n_streams: int = 2,
                 donate: bool = True):
        super().__init__(device, n_streams=n_streams, donate=donate)
        from repro.optim.offload import host_memory_kind
        kind = host_memory_kind(self._device)
        if kind is None:
            raise RuntimeError(
                f"{self._device} has no pinned_host memory: the pinned "
                f"backend cannot stage transfers on it")
        sds = self._jax.sharding.SingleDeviceSharding
        self._pinned = sds(self._device, memory_kind=kind)
        self._on_device = sds(self._device, memory_kind="device")

    def upload(self, host, *, stream: int = 0, name=None):
        staged = self._jax.device_put(host, self._pinned)
        handle = self._jax.device_put(staged, self._on_device)
        self._record(stream, Event(payload=handle))
        return handle

    def download(self, handle, *, stream: int = 0):
        return np.asarray(self._jax.device_put(handle, self._pinned))


_REGISTRY: Dict[str, Callable[[], Backend]] = {
    "numpy": NumpyHostBackend,
    "jax": JaxDeviceBackend,
    "pinned": PinnedHostBackend,
}


def register_backend(name: str, factory: Callable[[], Backend]) -> None:
    _REGISTRY[name] = factory
    _INSTANCES.pop(name, None)


_INSTANCES: Dict[str, Backend] = {}


def get_backend(spec: Any = None) -> Backend:
    """Resolve a backend: an instance passes through; ``None`` or a
    registered name returns a memoized process-wide instance — so jit
    caches and compiled-plan lowerings are reused across ``execute``
    calls no matter how the backend was named."""
    if isinstance(spec, Backend):
        return spec
    if spec is None:
        spec = "jax"
    if spec not in _INSTANCES:
        if spec == "mesh" and "mesh" not in _REGISTRY:
            # registered on import (distributed code never loads for
            # single-device callers otherwise)
            from repro.distributed import mesh_backend  # noqa: F401
        try:
            factory = _REGISTRY[spec]
        except KeyError:
            raise ValueError(
                f"unknown backend {spec!r}; have "
                f"{sorted(_REGISTRY)}") from None
        _INSTANCES[spec] = factory()
    return _INSTANCES[spec]
