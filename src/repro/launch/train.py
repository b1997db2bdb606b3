"""Training driver: fault-tolerant, checkpointed, straggler-aware.

    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --reduced \
        --steps 200 --mesh 1x1 --ckpt-dir /tmp/run1

Production features (DESIGN.md §9):
  * auto-resume from the latest complete checkpoint (atomic, keep-k);
  * step-addressable data (restart regenerates the exact stream);
  * straggler watchdog: per-step wall clock vs an EMA threshold; slow steps
    are logged and (configurably) trigger an early checkpoint so a
    replacement host can resume immediately;
  * preemption-safe: SIGTERM requests a checkpoint at the next step edge;
  * gradient compression (bf16 + error feedback) via --compress-grads;
  * elastic restart: checkpoints carry the mesh; restoring onto a different
    mesh re-shards per the current sharding rules (checkpoint/store.py).

On the CPU container this runs reduced configs on a 1x1 mesh; on real
hardware the same driver runs the full configs on the production mesh
(``--mesh 16x16`` / ``--mesh 2x16x16``).
"""
from __future__ import annotations

import argparse
import signal
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.config import ShapeConfig
from repro.configs import get_arch
from repro.data.pipeline import DataConfig, SyntheticLMData, make_global_batch
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.launch.steps import StepOptions, build_train_step
from repro.models import transformer as T
from repro.optim import AdamWConfig
from repro.parallel.sharding import batch_spec


class StragglerWatchdog:
    """EMA-based per-step wall-clock anomaly detector."""

    def __init__(self, factor: float = 3.0, warmup: int = 5):
        self.factor = factor
        self.warmup = warmup
        self.ema: float | None = None
        self.events: list[tuple[int, float]] = []
        self._n = 0

    def observe(self, step: int, dt: float) -> bool:
        """Returns True if this step is a straggler."""
        self._n += 1
        if self.ema is None:
            self.ema = dt
            return False
        slow = self._n > self.warmup and dt > self.factor * self.ema
        if slow:
            self.events.append((step, dt))
        # slow steps don't poison the EMA
        self.ema = 0.9 * self.ema + 0.1 * min(dt, self.factor * self.ema)
        return slow


def parse_mesh(spec: str):
    dims = tuple(int(x) for x in spec.split("x"))
    if len(dims) == 2:
        return make_mesh(dims, ("data", "model"))
    if len(dims) == 3:
        return make_mesh(dims, ("pod", "data", "model"))
    raise ValueError(f"mesh spec {spec!r}: want DxM or PxDxM")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--remat", default="full", choices=["none", "full", "dots"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = parse_mesh(args.mesh)
    shape = ShapeConfig("train", args.seq_len, args.global_batch, "train")
    options = StepOptions(remat=args.remat, compress_grads=args.compress_grads,
                          loss_chunk=min(512, args.seq_len))
    opt = AdamWConfig(lr=args.lr, moment_dtype=cfg.opt_state_dtype)

    step_fn, (p_sds, o_sds, b_sds) = build_train_step(
        cfg, mesh, shape, opt=opt, options=options
    )
    shardings = lambda t: jax.tree.map(lambda x: x.sharding, t)

    # ---- init or resume -------------------------------------------------
    mgr = CheckpointManager(args.ckpt_dir, mesh=mesh) if args.ckpt_dir else None
    start_step = 0
    if mgr is not None and mgr.latest() is not None:
        state_like = {"params": p_sds, "opt": o_sds}
        start_step, restored = mgr.restore_latest(
            state_like, shardings=shardings(state_like)
        )
        params, opt_state = restored["params"], restored["opt"]
        print(f"[train] resumed from step {start_step}", flush=True)
    else:
        params = jax.jit(
            lambda k: T.init_params(cfg, k), out_shardings=shardings(p_sds)
        )(jax.random.key(args.seed))
        opt_state = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype, device=s.sharding), o_sds
        )

    data = SyntheticLMData(
        DataConfig(vocab=cfg.vocab, seq_len=args.seq_len,
                   global_batch=args.global_batch, seed=args.seed)
    )
    spec = batch_spec(mesh, args.global_batch, args.seq_len)

    # ---- SIGTERM = checkpoint at the next step edge (preemption safety) --
    stop_requested = False

    def _on_term(signum, frame):
        nonlocal stop_requested
        stop_requested = True

    signal.signal(signal.SIGTERM, _on_term)

    watchdog = StragglerWatchdog()
    losses = []
    t_start = time.time()
    for step in range(start_step, args.steps):
        batch = make_global_batch(data, step, mesh, spec)
        t0 = time.time()
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        loss = float(metrics["loss"])  # blocks; also the step boundary
        dt = time.time() - t0
        losses.append(loss)
        if not np.isfinite(loss):
            print(f"[train] step {step}: NON-FINITE LOSS {loss}", flush=True)
            return 1
        if watchdog.observe(step, dt):
            print(f"[train] step {step}: straggler ({dt:.2f}s vs EMA "
                  f"{watchdog.ema:.2f}s) — checkpointing early", flush=True)
            if mgr is not None:
                mgr.save(step + 1, {"params": params, "opt": opt_state})
        if step % args.log_every == 0:
            print(f"[train] step {step:5d} loss {loss:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} {dt*1e3:.0f}ms",
                  flush=True)
        if mgr is not None and (step + 1) % args.ckpt_every == 0:
            mgr.save(step + 1, {"params": params, "opt": opt_state})
        if stop_requested:
            print(f"[train] SIGTERM: checkpoint at step {step + 1} and exit",
                  flush=True)
            if mgr is not None:
                mgr.save(step + 1, {"params": params, "opt": opt_state})
            return 0

    if mgr is not None:
        mgr.save(args.steps, {"params": params, "opt": opt_state})
    dt = time.time() - t_start
    print(f"[train] done: {args.steps - start_step} steps in {dt:.1f}s; "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}; "
          f"stragglers={len(watchdog.events)}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
