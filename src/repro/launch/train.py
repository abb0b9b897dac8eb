"""End-to-end training driver.

The host↔device schedule is the paper's optimized plan, realized on the
training loop:

  * advancedload — the data pipeline device_puts batch i+1 while step i runs
    (``PrefetchIterator``), and optimizer state streams in from pinned_host
    when ``--offload-opt`` (XLA-overlapped);
  * delegatestore — metrics stay on device and are fetched only at log
    steps (JAX async dispatch keeps the loop ahead); checkpoints copy
    device→host immediately and hit disk on a background thread;
  * noupdate — params/optimizer state never move (donated buffers);
  * synchronize — a single block_until_ready at log/checkpoint boundaries.

Fault tolerance: auto-resume from the latest checkpoint, optional injected
failures (--fail-at) exercising the restart path, straggler watchdog
logging.  Works on CPU with reduced configs (the smoke-scale path the tests
run) and is mesh-ready for real pods.

    PYTHONPATH=src python -m repro.launch.train --arch rwkv6-3b --reduced \
        --steps 30 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time
from typing import Optional

import jax

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, reduced as reduce_cfg
from repro.data import PrefetchIterator, SyntheticLM
from repro.launch.compile_cache import use_compile_cache
from repro.models import Transformer
from repro.optim import default_optimizer
from repro.runtime import FaultInjector, StepWatchdog

def make_train_step(model, opt):
    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            model.loss, has_aux=True)(params, batch)
        new_params, new_opt = opt.update(grads, opt_state, params)
        return new_params, new_opt, {"loss": loss, **metrics}
    return jax.jit(train_step, donate_argnums=(0, 1))


def train(cfg, *, steps: int, batch: int, seq: int, ckpt_dir: str,
          ckpt_every: int = 10, log_every: int = 5, seed: int = 0,
          injector: Optional[FaultInjector] = None,
          resume: bool = True) -> dict:
    model = Transformer(cfg)
    opt = default_optimizer(cfg)
    ckpt = CheckpointManager(ckpt_dir)
    watchdog = StepWatchdog()

    params = model.init(jax.random.key(seed))
    opt_state = opt.init(params)
    start_step = 0
    state_tree = {"params": params, "opt": opt_state}
    if resume:
        restored = ckpt.restore_latest(state_tree)
        if restored is not None:
            start_step, state_tree, extra = restored
            print(f"[train] resumed from step {start_step}")
    params, opt_state = state_tree["params"], state_tree["opt"]

    source = SyntheticLM(cfg, batch, seq, seed=seed)
    it = PrefetchIterator(source, start_index=start_step)   # advancedload
    step_fn = make_train_step(model, opt)

    losses = []
    t_start = time.perf_counter()
    try:
        for step in range(start_step, steps):
            t0 = time.perf_counter()
            batch_dev = next(it)
            if injector is not None:
                injector.maybe_fail(step)
            params, opt_state, metrics = step_fn(params, opt_state,
                                                 batch_dev)
            # metrics stay on device (delegatestore deferred until the
            # log step below forces the sync)
            if (step + 1) % log_every == 0 or step + 1 == steps:
                loss = float(metrics["loss"])      # ← the sync point
                losses.append((step + 1, loss))
                dt = time.perf_counter() - t0
                watchdog.record("host0", dt)
                print(f"[train] step {step + 1:5d} loss {loss:.4f} "
                      f"({dt * 1e3:.0f} ms)"
                      + (" STRAGGLER" if watchdog.stragglers() else ""))
            if (step + 1) % ckpt_every == 0 or step + 1 == steps:
                ckpt.save(step + 1,
                          {"params": params, "opt": opt_state},
                          extra={"data_index": step + 1})
    finally:
        it.close()
        ckpt.wait()                                # final synchronize
    wall = time.perf_counter() - t_start
    return {"losses": losses, "final_step": steps, "wall_s": wall,
            "params": params}


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config (CPU)")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--fail-at", type=int, nargs="*", default=[],
                    help="inject failures at these steps (restart demo)")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    injector = FaultInjector(tuple(args.fail_at)) if args.fail_at else None

    attempts = 0
    while True:
        try:
            out = train(cfg, steps=args.steps, batch=args.batch,
                        seq=args.seq, ckpt_dir=args.ckpt_dir,
                        ckpt_every=args.ckpt_every, injector=injector)
            break
        except RuntimeError as e:
            attempts += 1
            print(f"[train] FAILURE ({e}); restarting from latest "
                  f"checkpoint (attempt {attempts})")
            if attempts > 5:
                raise
    first = out["losses"][0][1] if out["losses"] else float("nan")
    last = out["losses"][-1][1] if out["losses"] else float("nan")
    print(f"[train] done: steps={out['final_step']} "
          f"loss {first:.4f} -> {last:.4f} wall={out['wall_s']:.1f}s "
          f"restarts={attempts}")


if __name__ == "__main__":
    main()
