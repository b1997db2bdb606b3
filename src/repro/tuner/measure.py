"""Measured trials: the tuner's ground truth (DESIGN.md §6).

The analytic model ranks; short timed trials decide.  Every trial runs
through the ordinary ``engine.multiply`` path, so the compiled programs it
builds land in (and are later served from) the plan layer's program cache —
tuning is not wasted work: the winning candidate's executable is already
hot when the application multiplies for real.

Timing discipline: one untimed warm-up call per candidate (compile +
cache fill), then ``reps`` *interleaved* timed rounds — each round times
every candidate once, blocking on the FULL output triple (blocks, mask,
norms: a lazily materialized buffer must not escape the clock) — keeping
the minimum per candidate.  Interleaving matters: machine-load drift during the pass
hits all candidates alike instead of biasing whichever happened to run
last, and the minimum filters one-off scheduler noise (the standard for
microbenchmarks of cached programs; cf. benchmarks/bench_plan_cache.py).
"""
from __future__ import annotations

import time
from dataclasses import dataclass

import jax

from repro.tuner.model import Candidate


@dataclass(frozen=True)
class Trial:
    candidate: Candidate
    seconds: float  # min over interleaved timed rounds of one multiply
    error: str = ""  # non-empty when the trial failed (candidate skipped;
    # never on a TPU, where a failing trial raises)

    @property
    def ok(self) -> bool:
        return not self.error


def measure_candidates(
    a,
    b,
    mesh,
    candidates,
    *,
    threshold: float = 0.0,
    interpret: bool | None = None,
    reps: int = 2,
) -> list[Trial]:
    """Time one multiply per candidate through the cached engine path.

    Operands may be replicated ``BlockSparseMatrix`` (mesh passed through)
    or ``ShardedBSM`` (already on the mesh — the trial measures exactly
    the device-resident path the application will run).  Off a TPU, a
    candidate that fails to build/execute is returned with its error
    instead of aborting the whole tuning pass.  On a TPU it raises: the
    model only offers candidates the chip can run, so a failure there
    (a Mosaic refusal, an allocation failure) is a fault to surface, not
    a lost race.
    """
    from repro.core.bsm import ShardedBSM
    from repro.core.engine import multiply

    sharded = isinstance(a, ShardedBSM)

    def make_run(c):
        def run():
            # sharded operands already live in their assignment's layout
            # (and carry it); only the replicated path runs the trial
            # under the candidate's block→device assignment
            return multiply(
                a, b, None if sharded else mesh,
                engine=c.engine, threshold=threshold, backend=c.backend,
                l=c.l, stack_capacity=c.stack_capacity, tile=c.tile,
                interpret=interpret, transport=c.transport,
                assignment=None if sharded else c.assign,
            )

        return run

    def wait(out):
        # block on the FULL output triple, not just the blocks: mask and
        # norms may materialize lazily (derived-norm algebra, async
        # dispatch), and a trial that stops the clock before they land
        # under-reports the candidate
        jax.block_until_ready((out.blocks, out.mask, out.norms))

    on_tpu = jax.default_backend() == "tpu"
    runners: dict[int, object] = {}
    best: dict[int, float] = {}
    errors: dict[int, str] = {}
    for i, cand in enumerate(candidates):
        run = make_run(cand)
        try:
            wait(run())  # warm-up: compile/caches
            runners[i] = run
            best[i] = float("inf")
        except Exception as e:  # noqa: BLE001 - surface per-candidate
            if on_tpu:
                raise
            errors[i] = repr(e)
    for _ in range(reps):  # interleaved rounds (see module docstring)
        for i, run in list(runners.items()):
            try:
                t0 = time.perf_counter()
                wait(run())
                best[i] = min(best[i], time.perf_counter() - t0)
            except Exception as e:  # noqa: BLE001 - contain per candidate
                if on_tpu:
                    raise
                errors[i] = repr(e)
                del runners[i]  # a failed candidate is out of the race
                del best[i]
    return [
        Trial(candidate=cand, seconds=best.get(i, float("inf")),
              error=errors.get(i, ""))
        for i, cand in enumerate(candidates)
    ]


def best_trial(trials) -> Trial:
    ok = [t for t in trials if t.ok]
    if not ok:
        raise ValueError(
            "every measured candidate failed: "
            + "; ".join(f"{t.candidate.label}: {t.error}" for t in trials)
        )
    return min(ok, key=lambda t: t.seconds)
