"""Multiplies C = A B, back to back, through ``engine.multiply``.

Set-up makes A and B from the seed and warms one multiply.  Each multiply
of the window is one call of ``engine.multiply`` with the traffic mix's
``backend`` on one device; the host time until the call returns (the
host's part: the pair-cube walk, the backend choice, the dispatch) is
recorded apart from the wait for the device.

The check compares one C of the window, drawn from the seed, with the
plain dense product of the same A and B (``reference/multiply.py``): the
relative Frobenius error.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchlib.work import product_flops
from reference import multiply as ref_multiply
from reference.dense import rel_err, to_dense


class Op:
    def __init__(self, cell, seed: int, devices, *, control: bool = False):
        from repro.core import bsm as B

        self.cell, self.seed, self.devices = cell, seed, devices
        self.control = control
        self.t = cell.traffic
        self.nb = cell.block_rows
        self.bs = int(cell.config["block_size"])
        self.rng = np.random.default_rng([seed, 0x4D55])
        made = cell.generator().make(cell.config, self.nb, seed)
        (ab, am), (bb, bm) = made["a"], made["b"]
        self.flops = product_flops(am, bm, self.bs, self.bs, self.bs)
        self.raw = (ab, am, bb, bm)
        if not control:
            self.a = B.make_bsm(ab, jnp.asarray(am))
            self.b = B.make_bsm(bb, jnp.asarray(bm))
            self.raw = None
        self.host_s = []
        self.sample = None

    def _multiply(self):
        from repro.core.engine import multiply

        return multiply(self.a, self.b, backend=self.t["backend"])

    def warm(self):
        if self.control:
            ab, am, bb, bm = self.raw
            jax.block_until_ready(
                ref_multiply.product(ab, am, bb, bm, precision="high"))
            return
        jax.block_until_ready(self._multiply().blocks)

    def step(self, k: int):
        with jax.profiler.TraceAnnotation("bench.multiply"):
            t0 = time.perf_counter()
            if self.control:
                ab, am, bb, bm = self.raw
                c = ref_multiply.product(ab, am, bb, bm, precision="high")
                out = (c, None)
            else:
                with jax.profiler.TraceAnnotation("bench.multiply_call"):
                    c = self._multiply()
                out = (c.blocks, c.mask)
            self.host_s.append(time.perf_counter() - t0)
            with jax.profiler.TraceAnnotation("bench.wait"):
                jax.block_until_ready(out[0])
        if self.rng.random() < 1.0 / (k + 1):
            self.sample = out

    def counters(self) -> dict:
        return {
            "multiplies": len(self.host_s),
            "failed": 0,
            "host_call_s": list(self.host_s),
            "useful_flops_per_step": self.flops,
        }

    def release(self):
        self.a = self.b = self.raw = None

    def check(self):
        limits = self.cell.limits
        blocks, mask = self.sample
        self.sample = None
        c = blocks if mask is None else to_dense(blocks, mask)
        made = self.cell.generator().make(self.cell.config, self.nb, self.seed)
        (ab, am), (bb, bm) = made["a"], made["b"]
        c_ref = ref_multiply.product(ab, am, bb, bm)
        del ab, bb, made
        return [("c_err", rel_err(c, c_ref), limits["c_err"])]
