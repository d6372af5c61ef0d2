"""The distributed Jacobi-PCG of an HPCG deployment, on four CPU devices.

The test process sees one CPU device, so one subprocess forces four host
devices (``XLA_FLAGS`` set before JAX starts there) and runs, on HPCG's
sym-scaled 27-point operator at 16 x 16 x 64 over four z-slabs of 16³
(``build_dist_plan``, ``ppermute`` exchange, fp16 words):

* ``jacobi_pcg_dist`` for 20 iterations (``tol`` 0) with float64 and with
  float32 solver vectors, against a plain scipy float64 Jacobi-PCG on
  ``A_q`` (the CSR with its values rounded to float16);
* the compiled HLO of the float64 solve with the recorder on and, from a
  plan built anew, with it off;
* the ``dist.*`` gauges and ``packsell.dist_build`` host spans of the
  build, beside counts taken from the CSR and the row blocks alone.

Each check below reads the one JSON line the subprocess prints.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from test_observe_spans import _strip_metadata

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NX, NY, NZ, SHARDS, ITERS = 16, 16, 64, 4, 20

_SCRIPT = r"""
import json, os, sys
import jax, jax.numpy as jnp, numpy as np

jax.config.update("jax_enable_x64", True)
from repro.core import testmats
from repro.distributed import build_dist_plan
from repro.observe import metrics as obs
from repro.solvers import cg
from repro.solvers import operators as op

nx, ny, nz, shards, iters, out_dir = sys.argv[1:]
nx, ny, nz, shards, iters = map(int, (nx, ny, nz, shards, iters))
assert jax.device_count() == shards, jax.devices()
a, _ = op.sym_scale(testmats.hpcg(nx, ny, nz))
a = a.tocsr()
n = a.shape[0]
diag = a.diagonal()
b = np.random.default_rng(16).standard_normal(n)


def plain_pcg(aq, d, b, iters):
    dinv = 1.0 / d
    x = np.zeros_like(b)
    r = b.copy()
    z = r * dinv
    p = z.copy()
    rz = r @ z
    for _ in range(iters):
        ap = aq @ p
        alpha = rz / (p @ ap)
        x += alpha * p
        r -= alpha * ap
        z = r * dinv
        rz_new = r @ z
        p = z + rz_new / rz * p
        rz = rz_new
    return x, np.linalg.norm(r) / np.linalg.norm(b)


def build():
    return build_dist_plan(a, shards, C=32, sigma=256, D=15, codec="fp16")


def solve(dplan, dtype):
    return cg.jacobi_pcg_dist(dplan, diag, jnp.asarray(b), tol=0.0,
                              maxiter=iters, dtype=dtype)


def hlo(dplan):
    fn = dplan.cached_fn(("pcg", 0.0, iters, "float64", dplan.exchange),
                         None)
    dinv = np.where(diag == 0, 1.0, 1.0 / diag)
    return fn.lower(dplan.dev, dplan.shard_vector(b),
                    dplan.shard_vector(dinv)).compile().as_text()


obs.enable(True)
dplan = build()
snap = obs.raw_snapshot()
gauges = {k[0]: v for k, v in snap["gauges"].items()
          if k[0].startswith("dist.")}
spans = {dict(k[1])["span"]: h["count"]
         for k, h in snap["histograms"].items()
         if k[0] == "span_s" and "dist_build" in dict(k[1])["span"]}

aq = a.copy()
aq.data = aq.data.astype(np.float32).astype(np.float16).astype(np.float64)
x_ref, relres_ref = plain_pcg(aq, diag, b, iters)
gaps = {}
for dtype in ("float64", "float32"):
    x, info = solve(dplan, getattr(jnp, dtype))
    x = np.asarray(x, np.float64)
    gaps[dtype] = {
        "iters": int(info.iters),
        "x_gap": float(np.linalg.norm(x - x_ref) / np.linalg.norm(x_ref)),
        "relres_gap": float(abs(float(info.relres) - relres_ref)
                            / relres_ref)}
with open(os.path.join(out_dir, "on.hlo"), "w") as f:
    f.write(hlo(dplan))
obs.enable(False)
off = build()
solve(off, jnp.float64)
with open(os.path.join(out_dir, "off.hlo"), "w") as f:
    f.write(hlo(off))

# counts from the CSR and the row blocks alone
rows_per = n // shards
halo, remote = [], 0
for p in range(shards):
    blk = a[p * rows_per:(p + 1) * rows_per].tocoo()
    outside = (blk.col < p * rows_per) | (blk.col >= (p + 1) * rows_per)
    halo.append(int(np.unique(blk.col[outside]).size))
    remote += int(outside.sum())
print(json.dumps({
    "gaps": gaps, "gauges": gauges, "spans": spans,
    "halo_per_shard": [int(c) for c in dplan.ops.maps.counts.sum(axis=1)],
    "counted": {"dist.shards": shards, "dist.halo_entries": sum(halo),
                "dist.h_pad": max(halo), "dist.remote_nnz": remote,
                "halo_per_shard": halo}}))
"""


@pytest.fixture(scope="module")
def dist_run(tmp_path_factory):
    out = tmp_path_factory.mktemp("dist_hpcg")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT, *map(str, (NX, NY, NZ, SHARDS,
                                                   ITERS)), str(out)],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    res["hlo"] = {side: (out / f"{side}.hlo").read_text()
                  for side in ("on", "off")}
    return res


# The program multiplies float16 values by x rounded to float32 and sums
# each row in float32; the reference takes every product and sum in
# float64. With float64 solver vectors that SpMV rounding (about 2^-24 of
# |A_q||x| per row) is the only difference: over 20 iterations it reads
# x_gap 1.2e-07 and relres_gap 2.4e-08 on the CPU. x_gap takes 100 times
# that, since CG amplifies a perturbation of the operator with the
# iteration count. relres_gap takes 1e-6: float32 solver vectors round
# every dot, norm and axpy of the recurrence and read 5.8e-06 there, while
# their x stays within the SpMV's own rounding (x_gap 1.7e-07).
TOL = {"x_gap": 1e-5, "relres_gap": 1e-6}


@pytest.mark.parametrize("number", sorted(TOL))
def test_dist_pcg_float64_matches_the_plain_reference(dist_run, number):
    g = dist_run["gaps"]["float64"]
    assert g["iters"] == ITERS
    assert g[number] <= TOL[number], g


def test_dist_pcg_float32_vectors_fail_a_tolerance(dist_run):
    g = dist_run["gaps"]["float32"]
    assert g["iters"] == ITERS
    assert any(g[k] > TOL[k] for k in TOL), g


@pytest.mark.parametrize("scope", ["packsell.halo", "packsell.allreduce",
                                   "packsell.gather_epilogue",
                                   "packsell.solver_vec/packsell.allreduce"])
def test_compiled_dist_solve_carries_the_scope(dist_run, scope):
    assert scope in dist_run["hlo"]["on"]
    assert "packsell." not in dist_run["hlo"]["off"]


def test_scopes_change_no_compiled_instruction_of_the_dist_solve(dist_run):
    on, off = dist_run["hlo"]["on"], dist_run["hlo"]["off"]
    assert _strip_metadata(on) == _strip_metadata(off)


@pytest.mark.parametrize("gauge", ["dist.shards", "dist.halo_entries",
                                   "dist.h_pad", "dist.remote_nnz"])
def test_dist_gauges_equal_the_partition_counts(dist_run, gauge):
    assert dist_run["gauges"][gauge] == dist_run["counted"][gauge]


def test_interior_slabs_receive_two_faces_and_end_slabs_one(dist_run):
    face = NX * NY
    want = [face, 2 * face, 2 * face, face]
    assert dist_run["counted"]["halo_per_shard"] == want
    assert dist_run["halo_per_shard"] == want


@pytest.mark.parametrize("span", [
    "packsell.dist_build", "packsell.dist_build.partition",
    "packsell.dist_build.members", "packsell.dist_build.stack",
    "packsell.dist_build.to_device"])
def test_dist_build_records_its_span_once(dist_run, span):
    assert dist_run["spans"].get(span) == 1
