"""Traffic kind ``spmv_synced``: one caller in a closed loop. Each
``SpMVPlan.spmv`` call is dispatched and waited on (``block_until_ready``)
before the next; x cycles through ``rhs_pool`` standard-normal float32
vectors drawn from the seed and placed on the device in set-up. A uniform
sample of the window's answers, drawn from the seed, is kept for the
reference."""
from __future__ import annotations

import time

import numpy as np

from perfbench import drivers, reference


class Driver:
    sample = 16         # answers kept for the check, a uniform sample

    def __init__(self, cfg: dict, mix: dict, seed: int, say):
        self.cfg, self.mix, self.seed, self.say = cfg, mix, seed, say

    def setup(self) -> dict:
        import jax

        op = drivers.build_operator(self.cfg, self.say)
        self.a, self.mat, self.plan = op["a"], op["mat"], op["plan"]
        self.draw(self.seed)
        jax.block_until_ready(self.plan.spmv(self.mat, self.xs[0]))
        return op

    def draw(self, seed: int) -> None:
        """The x pool of ``seed``, on the host and on the device."""
        import jax

        self.seed = seed
        rng = np.random.default_rng(seed)
        k = int(self.mix["rhs_pool"])
        self.x_host = rng.standard_normal((k, self.a.shape[1])) \
            .astype(np.float32)
        self.xs = [jax.device_put(x) for x in self.x_host]
        jax.block_until_ready(self.xs)

    def window(self, seconds: float) -> dict:
        plan, mat, xs = self.plan, self.mat, self.xs
        keep = drivers.Reservoir(self.sample, self.seed)
        lat = []
        t_start = time.perf_counter()
        with drivers.span("bench.window"):
            while True:
                i = len(lat)
                t0 = time.perf_counter()
                with drivers.span("bench.dispatch"):
                    y = plan.spmv(mat, xs[i % len(xs)])
                with drivers.span("bench.wait"):
                    y.block_until_ready()
                t1 = time.perf_counter()
                lat.append(t1 - t0)
                keep.offer((i, y))
                if t1 - t_start >= seconds:
                    break
        window_s = t1 - t_start
        self.kept = keep.items
        lat_ms = np.asarray(lat) * 1e3
        return {"window_s": window_s, "calls": len(lat),
                "slowest": drivers.slowest(lat),
                "e2e": {"spmv_ms": window_s / len(lat) * 1e3,
                        "spmv_p95_ms": float(np.percentile(lat_ms, 95))}}

    def hlo_texts(self) -> list:
        """Compiled HLO of the window's program (from the compile cache)."""
        plan = self.plan
        return [plan._dispatch("spmv").lower(
            plan._exec_mat(self.mat), plan._device_operands(), self.xs[0],
            False).compile().as_text()]

    def answers(self) -> list:
        """The kept answers on the host, as ``(call, slot, y)``."""
        return [(i, i % len(self.xs), np.asarray(y)) for i, y in self.kept]

    def free(self) -> None:
        del self.mat, self.plan, self.xs, self.kept

    def check(self, answers: list, prec: str) -> list:
        """Per kept call, ``{"y_gap": ...}`` against the reference."""
        ref = reference.SpMVReference(self.a, prec)
        by_slot = {}
        out = []
        for _, slot, y in answers:
            if slot not in by_slot:
                by_slot[slot] = ref.solve(self.x_host[slot])
            out.append({"y_gap": ref.gap(y, *by_slot[slot])})
        return out

    def control_answers(self, key: str, lower: str):
        """The answers of the reference for every slot of the drawn pool,
        with the configuration's ``precision[key]`` one step ``lower``,
        shaped as ``answers()``; None where this kind has no such key."""
        values = self.cfg["precision"]["values"]
        if key == "values":
            aq, xs = reference.stored_operator(self.a, lower), self.x_host
        elif key == "x":
            aq = reference.stored_operator(self.a, values)
            xs = [reference.round_values(x, lower) for x in self.x_host]
        else:
            return None
        return [(s, s, (aq @ np.asarray(x, np.float64)).astype(np.float32))
                for s, x in enumerate(xs)]
