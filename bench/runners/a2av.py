"""Runner: the NIMBLE All-to-Allv as a collective, ``NimbleAllToAll.__call__``
under ``shard_map``, one call at a time.

Every source holds one [C, E] buffer per destination; the traffic sets how
many chunks of each are live.  A call all-gathers the counts, plans the flows
and moves the live chunks over the scheduled paths.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench import generate, harness, work


def bits(a):
    """The array as unsigned integers of its width, for bitwise equality."""
    return jax.lax.bitcast_convert_type(
        a, {2: jnp.uint16, 4: jnp.uint32}[a.dtype.itemsize])


class Runner:
    def __init__(self, cell, seed: int, devices):
        self.cell, self.seed, self.devices = cell, seed, list(devices)
        self.failed = 0

    def setup(self):
        from repro.core.dataplane import NimbleAllToAll

        c, t = self.cell.config, self.cell.traffic
        n = self.n = int(c["n_devices"])
        if len(self.devices) != n:
            raise ValueError(f"{c['name']} runs on {n} chips, "
                             f"given {len(self.devices)}")
        dtype = jnp.dtype(c["dtype"])
        self.chunk_bytes = int(c["chunk_bytes"])
        C, E = int(c["max_chunks"]), self.chunk_bytes // dtype.itemsize
        live = int(t["per_rank_bytes"]) // self.chunk_bytes
        if live > C:
            raise ValueError(f"{live} live chunks per rank exceed C={C}")
        self.hot = int(np.random.default_rng([self.seed, 0xA2]).integers(n))
        self.counts = generate.hot_spot_counts(n, live, t["hot_ratio"],
                                               self.hot)
        harness.log(
            f"demand (chunks of {self.chunk_bytes} B, source x destination): "
            f"{self.counts.tolist()}; hot destination {self.hot} takes "
            f"{self.counts[:, self.hot].sum() / self.counts.sum()} of all "
            f"chunks")
        mesh = Mesh(np.array(self.devices), ("x",))
        self.shard = NamedSharding(mesh, P("x"))
        key = harness.seed_key(self.seed)
        mask = jnp.asarray(self.counts.reshape(n * n))

        def make(key, mask):
            x = jax.random.normal(key, (n * n, C, E), dtype)
            live = jnp.arange(C)[None, :] < mask[:, None]
            return jnp.where(live[..., None], x, jnp.zeros((), dtype))

        make = jax.jit(make, out_shardings=self.shard)
        self.xs = [make(jax.random.fold_in(key, j), mask)
                   for j in range(int(t["payload_sets"]))]
        self.c_dev = jax.device_put(mask.astype(jnp.int32), self.shard)
        comm = NimbleAllToAll("x", n, int(c["group_size"]), max_chunks=C,
                              chunk_bytes=float(self.chunk_bytes),
                              alt_frac=float(c["alt_frac"]), mode=c["mode"])
        self.fn = jax.jit(jax.shard_map(
            comm, mesh=mesh, in_specs=(P("x"), P("x")),
            out_specs=(P("x"), P("x"))))
        jax.block_until_ready(self.fn(self.xs[0], self.c_dev))
        self.useful = work.a2av_useful_bytes(self.counts, self.chunk_bytes)

    @property
    def hot_device_id(self) -> int:
        return self.devices[self.hot].id

    def step(self, i: int):
        return self.fn(self.xs[i % len(self.xs)], self.c_dev)

    def end_to_end(self, win) -> dict:
        return {"a2av_GBps": win.calls * self.useful / win.seconds / 1e9,
                "a2av_p95_ms": harness.p95(win.latencies) * 1e3}

    def work(self) -> dict:
        return {"useful_bytes_per_call": self.useful,
                "hot_ingress_bytes": work.a2av_ingress_bytes(
                    self.counts, self.chunk_bytes, self.hot)}

    def free(self):
        self.fn = None

    def check(self, samples) -> dict:
        """Every element of every sampled answer bit for bit, and its receive
        counts, against the reference."""
        ref = self.cell.reference()
        n = self.n
        f_ref = jax.jit(lambda x, c: ref.all_to_allv(x, c, n),
                        out_shardings=(self.shard, self.shard))
        wrong = jax.jit(lambda a, b: jnp.sum(bits(a) != bits(b)))
        bad_bits = bad_recv = 0
        for i, (y, recv) in samples:
            y_ref, r_ref = f_ref(self.xs[i % len(self.xs)], self.c_dev)
            b, r = int(wrong(y, y_ref)), int(jnp.sum(recv != r_ref))
            harness.log(f"answer of call {i}: {b} elements and {r} receive "
                        f"counts differ")
            self.failed += int(b > 0 or r > 0)
            bad_bits += b
            bad_recv += r
        lim = self.cell.limits
        return {"bits_wrong": (bad_bits, lim["bits_wrong"]),
                "recv_wrong": (bad_recv, lim["recv_wrong"])}
