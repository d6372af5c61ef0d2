"""Traffic kind ``pcg_sets_dist``: the HPCG-style timed sets of
``pcg_sets`` on the program's distributed path. The CSR is partitioned over
the configuration's ``shards`` devices, one contiguous row block per chip
(``distributed.build_dist_plan``, halo exchange by ``ppermute``), and each
set is one ``cg.jacobi_pcg_dist`` solve from x0 = 0 for exactly
``iters_per_set`` iterations (``tol`` 0) with float64 solver vectors, every
dot and norm reduced over the mesh. The b pool, the window, the sample of
its sets, the check against the plain reference (on the whole CSR) and
the controls are ``pcg_sets``'s.

On a TPU the kind refuses to run with fewer chips than ``shards``. Off a
TPU it partitions over as many devices as the process has, up to
``shards``, so that a CPU rehearsal runs on one device or on several
forced host devices."""
from __future__ import annotations

import time

import numpy as np

from perfbench import registry

PCGSets = registry.load_kind("pcg_sets")


class Driver(PCGSets):

    def _solve(self, b, dtype="float64"):
        import jax.numpy as jnp

        from repro.solvers import cg

        return cg.jacobi_pcg_dist(self.dplan, self.diag, b,
                                  tol=float(self.mix["tol"]),
                                  maxiter=self.iters,
                                  dtype=getattr(jnp, dtype))

    def _devices(self) -> list:
        import jax

        devs = jax.devices()
        want = int(self.cfg["shards"])
        if devs[0].platform == "tpu" and len(devs) < want:
            raise RuntimeError(f"the configuration has {want} shards, one "
                               f"per chip; JAX found {len(devs)} chips")
        if len(devs) < want:
            self.say(f"{devs[0].platform}: {len(devs)} devices, so "
                     f"{len(devs)} of the configuration's {want} shards")
        return devs[:want]

    def setup(self) -> dict:
        import jax

        from repro.distributed import build_dist_plan

        devs = self._devices()
        t0 = time.perf_counter()
        self.a = registry.load_generator(self.cfg["matrix"]["generator"])(
            self.cfg["matrix"])
        t_matrix = time.perf_counter() - t0
        fmt = self.cfg["format"]
        t0 = time.perf_counter()
        self.dplan = build_dist_plan(self.a, len(devs), devices=devs,
                                     C=fmt["C"], sigma=fmt["sigma"],
                                     D=fmt["D"], codec=fmt["codec"])
        jax.block_until_ready(self.dplan.dev)
        build_s = time.perf_counter() - t0
        ops = self.dplan.ops
        members = [(dm.label, dm.plans and dm.plans[0].cache_mode)
                   for dm in ops.members]
        self.say(f"matrix {self.cfg['matrix']['generator']}: "
                 f"n={self.a.shape[0]} nnz={self.a.nnz} built in "
                 f"{t_matrix:.3f}s")
        self.say(f"dist plan {build_s:.3f}s: {self.dplan.n_shards} shards "
                 f"on {[str(d) for d in devs]}, exchange="
                 f"{self.dplan.exchange}, rows per shard "
                 f"{[int(c) for c in ops.part.counts]}, halo entries per "
                 f"shard {[int(c) for c in ops.maps.counts.sum(axis=1)]}, "
                 f"h_pad={ops.h_pad}, members {members}")
        self.diag_host = self.a.diagonal()
        self.diag = jax.device_put(self.diag_host)
        self.draw(self.seed)
        for _ in range(int(self.mix["warmup_sets"])):
            jax.block_until_ready(self._solve(self.bs[0]))
        return {"a": self.a, "dplan": self.dplan, "build_s": build_s}

    def hlo_texts(self) -> list:
        """Compiled HLO of the window's ``shard_map`` program: the solve
        ``jacobi_pcg_dist`` parked on the plan (``cached_fn``)."""
        key = ("pcg", float(self.mix["tol"]), self.iters, "float64",
               self.dplan.exchange)

        def missing():
            raise KeyError(f"the plan holds no solve under {key}")

        fn = self.dplan.cached_fn(key, missing)
        dinv = np.where(self.diag_host == 0, 1.0, 1.0 / self.diag_host)
        return [fn.lower(self.dplan.dev,
                         self.dplan.shard_vector(self.b_host[0]),
                         self.dplan.shard_vector(dinv)).compile().as_text()]

    def free(self) -> None:
        del self.dplan, self.bs, self.diag, self.kept
