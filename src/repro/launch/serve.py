"""Serving driver: batched prefill + decode with the slot engine.

    PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b --reduced \
        --batch 4 --max-new 16

Runs a batch of synthetic prompts through the ServingEngine (continuous
slot batching, greedy or temperature sampling) and reports tokens/s.  On
real hardware the same driver serves the full configs on the production
mesh; the decode-step sharding comes from the same rules as the dry-run's
``decode_*`` cells (serve options default to fsdp_axis=None — weights
replicated over `data`, sharded over `model` — because decode all-gathers
of FSDP-sharded weights per token dominate otherwise; see EXPERIMENTS §Perf).

``--queue N`` drains N requests through continuous batching (slot refill)
instead of one static round.  ``--tuning-db`` binds the tuner database so
serving-dispatch decisions persist across launches (the DB as a
serving-time asset, DESIGN.md §11); without it the engine falls back to
the static analytic decision.  ``--moe-impl spgemm`` routes MoE expert
dispatch through the block-sparse SpGEMM stack under a covering decode
envelope resolved per pattern bucket.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time

import jax
import numpy as np

from repro.configs import get_arch
from repro.launch.cache import enable_compile_cache
from repro.models import transformer as T
from repro.serving.engine import GenerationConfig, ServingEngine


def _dispatch_spec(cfg, batch: int):
    """Covering decode-grid dispatch spec, resolved through the bucket
    cache (decision from the bound tuning DB when one is set)."""
    from repro.core.envelope import DispatchCache
    from repro.models.moe import DispatchSpec, moe_dims

    e, _ = moe_dims(cfg)
    tb = cfg.moe.token_block
    nb = (batch + tb - 1) // tb
    # static fallback envelope: covers every routing of the decode grid,
    # so no request is ever clipped; selective warmed envelopes come from
    # calibration traffic (benchmarks/bench_serving.py)
    full = np.ones((nb, e), bool)
    cache = DispatchCache(np.eye(e, dtype=bool), dtype=str(cfg.dtype))
    env, dec = cache.resolve(full)
    return DispatchSpec(envelope=env, backend=dec["backend"],
                        stack_capacity=dec["capacity"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--queue", type=int, default=0,
                    help="drain N requests through continuous batching "
                         "(0 = one static generate round)")
    ap.add_argument("--moe-impl", default=None,
                    help="override cfg.moe.impl (e.g. spgemm) for MoE archs")
    ap.add_argument("--tuning-db", default=None,
                    help="tuning database path (created if missing); "
                         "omitted = static decisions only")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.tuning_db:
        from repro import tuner
        from repro.core import plan as plan_mod

        plan_mod.clear_cache()
        tuner.set_default_db(args.tuning_db)

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.moe_impl:
        if cfg.moe is None:
            raise SystemExit(f"--moe-impl: arch {args.arch} has no MoE")
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, impl=args.moe_impl))
    params = T.init_params(cfg, jax.random.key(args.seed))

    gen = GenerationConfig(max_new_tokens=args.max_new,
                           temperature=args.temperature, seed=args.seed)
    engine = ServingEngine(cfg, params, batch=args.batch,
                           max_len=args.max_len, gen=gen)
    if cfg.moe is not None and cfg.moe.impl == "spgemm":
        spec = _dispatch_spec(cfg, args.batch)
        engine.set_dispatch(spec)
        print(f"[serve] spgemm dispatch: capacity={spec.stack_capacity} "
              f"backend={spec.backend}")

    rng = np.random.default_rng(args.seed)
    n_req = args.queue if args.queue > 0 else args.batch
    prompts = [rng.integers(0, cfg.vocab, size=args.prompt_len).astype(np.int32)
               for _ in range(n_req)]

    t0 = time.time()
    if args.queue > 0:
        outs = engine.serve(prompts)
        st = engine.last_serve_stats
        occ = (sum(s["occupancy"] for s in st["steps"]) / len(st["steps"])
               if st["steps"] else 0.0)
        print(f"[serve] queue drained: {st['n_requests']} requests, "
              f"{st['n_refills']} refills, mean occupancy {occ:.2f}")
    else:
        outs = engine.generate(prompts)
    dt = time.time() - t0
    n_tokens = sum(len(o) for o in outs)
    print(f"[serve] {n_req} requests, {n_tokens} tokens in {dt:.2f}s "
          f"({n_tokens / dt:.1f} tok/s incl. compile)")
    for i, o in enumerate(outs[: min(4, len(outs))]):
        print(f"[serve] req{i}: {o[:12]}{'...' if len(o) > 12 else ''}")

    if args.tuning_db:
        from repro import tuner

        db = tuner.get_default_db()
        print(f"[serve] tuning db: {len(db)} record(s) at {db.path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
