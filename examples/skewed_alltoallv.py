"""Skewed All-to-Allv on the REAL NIMBLE dataplane (8 forced host devices).

This is the executable counterpart of quickstart.py: instead of simulating a
plan, it runs the actual ``shard_map`` dataplane — live demand matrix ->
jittable MWU planner -> scheduled ``lax.ppermute`` rounds — and verifies the
result bit-exactly against a numpy oracle for all three modes, under a
hotspot-ratio sweep (paper Fig. 7 setup: 8 ranks = 2 nodes x 4 GPUs).
The dataplane endpoints come ready-wired from one ``repro.api.Session``
(``session.all_to_all``, DESIGN.md §5).

Because the container is CPU-only, wall-clock here is NOT bandwidth — the
projected completion times come from the planner's own link-time model
(printed alongside), which benchmarks/bench_alltoallv_skew.py validates
against the paper's 5.2x claim.

Run:
    PYTHONPATH=src python examples/skewed_alltoallv.py
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.api import Session, SessionSpec, TopologySpec
from repro.core import fabsim
from repro.core.dataplane import ref_all_to_allv
from repro.launch.selftest import hot_spot_counts


def main():
    n, C, E = 8, 32, 64               # 8 ranks, <=32 chunks/dst, 64 floats each
    mesh = Mesh(np.array(jax.devices()[:n]), ("x",))
    rng = np.random.default_rng(0)

    spec = SessionSpec(topology=TopologySpec(n_devices=n, group_size=4))
    with Session(spec) as sess:
        for hotspot in [0.3, 0.7, 0.9]:
            counts = hot_spot_counts(n, C, hotspot)
            x_all = rng.normal(size=(n, n, C, E)).astype(np.float32)
            for s in range(n):
                for d in range(n):
                    x_all[s, d, counts[s, d]:] = 0.0
            yref, rref = ref_all_to_allv(x_all, counts)

            print(f"\nhotspot={hotspot}")
            for mode in ["direct", "stripe", "nimble"]:
                comm = sess.all_to_all("x", max_chunks=C, chunk_bytes=E * 4,
                                       mode=mode)
                fn = jax.shard_map(lambda x, c: comm(x, c), mesh=mesh,
                               in_specs=(P("x"), P("x")),
                               out_specs=(P("x"), P("x")))
                y, r = jax.jit(fn)(jnp.asarray(x_all.reshape(n * n, C, E)),
                                   jnp.asarray(counts.reshape(n * n)))
                ok = (np.allclose(np.asarray(y).reshape(n, n, C, E), yref)
                      and np.array_equal(np.asarray(r).reshape(n, n), rref))

                # projected completion time on the calibrated fabric
                demands = {(s, d): float(counts[s, d]) * E * 4 * 2**14
                           for s in range(n) for d in range(n)
                           if counts[s, d]}
                t = fabsim.simulate(
                    sess.plan(demands, mode=mode)
                ).completion_time
                print(f"  {mode:7s} bit-exact={'OK' if ok else 'FAIL'}   "
                      f"projected completion {t * 1e3:8.3f} ms")
                assert ok, f"dataplane {mode} mismatch"
    print("\nall modes bit-exact vs oracle")


if __name__ == "__main__":
    main()
