"""Traffic kind ``pcg_sets``: HPCG-style timed sets. Each set is one
independent ``cg.jacobi_pcg_stored`` solve from x0 = 0 for exactly
``iters_per_set`` iterations (``tol`` 0) with float64 solver vectors; b
cycles through ``rhs_pool`` standard-normal vectors drawn from the seed.
The window ends with the first set that finishes after ``--seconds``. A
uniform sample of the window's sets, drawn from the seed, is kept for the
reference."""
from __future__ import annotations

import time

import numpy as np

from perfbench import drivers, reference


class Driver:
    sample = 4          # sets kept for the check, a uniform sample

    def __init__(self, cfg: dict, mix: dict, seed: int, say):
        self.cfg, self.mix, self.seed, self.say = cfg, mix, seed, say
        self.iters = int(mix["iters_per_set"])

    def _solve(self, b, dtype="float64"):
        import jax.numpy as jnp

        from repro.solvers import cg

        return cg.jacobi_pcg_stored(self.mat, self.plan, self.diag, b,
                                    tol=float(self.mix["tol"]),
                                    maxiter=self.iters,
                                    dtype=getattr(jnp, dtype))

    def setup(self) -> dict:
        import jax

        op = drivers.build_operator(self.cfg, self.say)
        self.a, self.mat, self.plan = op["a"], op["mat"], op["plan"]
        self.diag_host = self.a.diagonal()
        self.diag = jax.device_put(self.diag_host)
        self.draw(self.seed)
        for _ in range(int(self.mix["warmup_sets"])):
            jax.block_until_ready(self._solve(self.bs[0]))
        return op

    def draw(self, seed: int) -> None:
        """The b pool of ``seed``, on the host and on the device."""
        import jax

        self.seed = seed
        rng = np.random.default_rng(seed)
        k = int(self.mix["rhs_pool"])
        self.b_host = rng.standard_normal((k, self.a.shape[0]))
        self.bs = [jax.device_put(b) for b in self.b_host]
        jax.block_until_ready(self.bs)

    def window(self, seconds: float) -> dict:
        import jax

        keep = drivers.Reservoir(self.sample, self.seed)
        times = []
        t_start = time.perf_counter()
        with drivers.span("bench.window"):
            while True:
                t0 = time.perf_counter()
                with drivers.span("bench.dispatch"):
                    x, info = self._solve(self.bs[len(times) % len(self.bs)])
                with drivers.span("bench.wait"):
                    jax.block_until_ready((x, info))
                keep.offer((len(times), x, info))
                t1 = time.perf_counter()
                times.append(t1 - t0)
                if t1 - t_start >= seconds:
                    break
        window_s = t1 - t_start
        self.kept = keep.items
        iters = len(times) * self.iters
        return {"window_s": window_s, "calls": len(times), "iterations": iters,
                "slowest": drivers.slowest(times),
                "e2e": {"pcg_iter_ms": window_s / iters * 1e3}}

    def hlo_texts(self) -> list:
        """Compiled HLO of the window's program (from the compile cache)."""
        import jax.numpy as jnp

        from repro.solvers import cg

        b = self.bs[0]
        fn = cg.stored_solve_fn(self.plan, b, tol=float(self.mix["tol"]),
                                maxiter=self.iters, dtype=jnp.float64)
        x0 = jnp.zeros((self.plan.total_stored,), jnp.float64)
        return [fn.lower(self.mat, self.plan._device_operands(), self.diag,
                         b, x0).compile().as_text()]

    def answers(self) -> list:
        return [(i, i % len(self.bs), np.asarray(x),
                 float(info.relres), int(info.iters))
                for i, x, info in self.kept]

    def free(self) -> None:
        del self.mat, self.plan, self.bs, self.diag, self.kept

    def check(self, answers: list, prec: str) -> list:
        aq = reference.stored_operator(self.a, prec)
        by_slot = {}
        out = []
        for _, slot, x, relres, iters in answers:
            if slot not in by_slot:
                by_slot[slot] = reference.pcg(aq, self.diag_host,
                                              self.b_host[slot], self.iters)
            gaps = reference.pcg_gaps(x, relres, *by_slot[slot])
            gaps["iters_off"] = float(abs(iters - self.iters))
            out.append(gaps)
        return out

    def control_answers(self, key: str, lower: str):
        """The answers for every slot of the drawn pool with the
        configuration's ``precision[key]`` one step ``lower``, shaped as
        ``answers()``: for ``values`` the reference with its values
        rounded lower, for ``solver_vectors`` the program's own solve
        with vectors of that dtype. None where this kind has no such
        key."""
        out = []
        for s, b in enumerate(self.b_host):
            if key == "values":
                aq = reference.stored_operator(self.a, lower)
                x, relres = reference.pcg(aq, self.diag_host, b, self.iters)
                out.append((s, s, x, relres, self.iters))
            elif key == "solver_vectors":
                x, info = self._solve(self.bs[s], dtype=lower)
                out.append((s, s, np.asarray(x), float(info.relres),
                            int(info.iters)))
            else:
                return None
        return out
