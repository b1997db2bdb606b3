"""Whole purifications, back to back: the SCF loop of ``launch/purify.py``.

Set-up makes H from the seed, shards it once onto the cell's square
(r, c) mesh (``bsm.shard_bsm``) and warms one sync block of the chain.
Purification k of the window computes P = (I - sign(H_k - mu I)) / 2 with
``signiter.density_matrix(mode="fused")`` for H_k = (1 + rescale * k) H:
the same pattern every time, so every program is reused.

The check compares one purification of the window, drawn from the seed,
with the plain dense Newton-Schulz reference of the same H_k
(``reference/purify.py``): the relative error of P, its idempotency
||P^2 - P|| / ||P||, and, over every purification of the window, the
largest |trace(P) - occupied| and the number that did not converge.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from reference import purify as ref_purify
from reference.dense import matmul, rel_err, to_dense


@jax.jit
def _trace(blocks, mask):
    idx = jnp.arange(blocks.shape[0])
    diag = jnp.trace(blocks[idx, idx], axis1=-2, axis2=-1)
    return jnp.sum(jnp.where(mask[idx, idx], diag, 0.0))


class Op:
    def __init__(self, cell, seed: int, devices, *, control: bool = False):
        from repro.core import bsm as B
        from repro.launch.mesh import make_spgemm_mesh

        self.cell, self.seed, self.devices = cell, seed, devices
        self.control = control
        self.t = cell.traffic
        cfg = cell.config
        self.nb = cell.block_rows
        self.bs = int(cfg["block_size"])
        self.n_occ = int(cfg["occupied_per_block"]) * self.nb
        self.threshold = float(cfg["threshold"])
        self.filter_eps = float(cfg["filter_eps"])
        self.rng = np.random.default_rng([seed, 0x5A4D])
        p = math.isqrt(len(devices))
        self.mesh = make_spgemm_mesh(p=p, devices=devices)
        # H is born in the mesh layout: no chip holds the whole of it
        home = NamedSharding(self.mesh, PartitionSpec("r", "c", None, None))
        blocks, mask = cell.generator().make(
            cfg, self.nb, seed, sharding=None if control else home)["h"]
        if control:
            self.h_dense = to_dense(blocks, mask)
            self.h = None
        else:
            h = B.make_bsm(blocks, jnp.asarray(mask))
            self.h = B.shard_bsm(h, self.mesh)
            del h
        del blocks
        self.stats, self.traces = [], []
        self.sample = None  # (k, P blocks, P mask) of the sampled step

    def _scale(self, k: int) -> float:
        return 1.0 + float(self.t["rescale_step"]) * k

    def _purify(self, h, max_iter: int):
        from repro.core.signiter import density_matrix

        return density_matrix(
            h, float(self.t["mu"]), engine=self.t["engine"],
            threshold=self.threshold, filter_eps=self.filter_eps,
            max_iter=max_iter, tol=float(self.t["tol"]), mode="fused",
            sync_every=int(self.t["sync_every"]))

    def warm(self):
        if self.control:
            ref_purify.density_matrix(self.h_dense, 0.0, max_iter=1,
                                      precision="high")
            return
        # one sync block of the chain: the sweep program, the shift and
        # projector algebra, the rescale and the trace, at the window's
        # shapes
        p, _ = self._purify(self.h.scale(self._scale(1)),
                            int(self.t["sync_every"]))
        jax.block_until_ready(_trace(p.blocks, p.mask))

    def step(self, k: int):
        with jax.profiler.TraceAnnotation("bench.purify"):
            if self.control:
                x = self.h_dense * self._scale(k)
                pd, it = ref_purify.density_matrix(
                    x, float(self.t["mu"]), tol=float(self.t["tol"]),
                    max_iter=int(self.t["max_iter"]), precision="high")
                blocks = pd.reshape(self.nb, self.bs, self.nb, self.bs)
                blocks = blocks.transpose(0, 2, 1, 3)
                mask = jnp.ones((self.nb, self.nb), bool)
                self.stats.append((it, True, []))
            else:
                p, st = self._purify(self.h.scale(self._scale(k)),
                                     int(self.t["max_iter"]))
                blocks, mask = p.blocks, p.mask
                self.stats.append((st.iterations, st.converged,
                                   st.occupancy_trace))
            tr = _trace(blocks, mask)
            with jax.profiler.TraceAnnotation("bench.wait"):
                jax.block_until_ready(tr)
        self.traces.append(tr)
        # reservoir sample of one purification, drawn from the seed
        if self.rng.random() < 1.0 / (k + 1):
            self.sample = (k, blocks, mask)

    def counters(self) -> dict:
        iters = [s[0] for s in self.stats]
        occ = [o for s in self.stats for o in s[2]]
        failed = sum(1 for s in self.stats if not s[1])
        return {
            "purifications": len(self.stats) - failed,
            "failed": failed,
            "sweeps": iters,
            "x_occupancy": occ,
        }

    def release(self):
        self.h = None
        self.h_dense = None
        self.traces = [float(t) for t in self.traces]

    def check(self):
        limits = self.cell.limits
        k, blocks, mask = self.sample
        dev0 = self.devices[0]
        p = to_dense(jax.device_put(blocks, dev0), jax.device_put(mask, dev0))
        self.sample = None
        with jax.default_device(dev0):
            hb, hm = self.cell.generator().make(self.cell.config, self.nb,
                                                self.seed)["h"]
            h = to_dense(jax.device_put(hb, dev0), hm) * self._scale(k)
            del hb
            p_ref, _ = ref_purify.density_matrix(
                h, float(self.t["mu"]), tol=float(self.t["tol"]),
                max_iter=int(self.t["max_iter"]))
            del h
            p_err = rel_err(p, p_ref)
            del p_ref
            idem = rel_err(matmul(p, p), p)
        trace_err = max(abs(t - self.n_occ) for t in self.traces)
        unconverged = sum(1 for s in self.stats if not s[1])
        return [
            ("p_err", p_err, limits["p_err"]),
            ("idempotency", idem, limits["idempotency"]),
            ("trace_err", trace_err, limits["trace_err"]),
            ("unconverged", float(unconverged), 0.0),
        ]
