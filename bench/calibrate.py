"""Readings that a cell's limits are set from, on the chip, in one process:
the program's runs over many seeds, the control (the plain reference put in
the program's place at the next precision down) and planted faults.

    python bench/calibrate.py --workload <cell> --seeds 1 2 ... \
        --control-seeds 1 2 3 --faults <fault> ... --fault-seeds 1 2 3

Each run prints one line: which reading, the seed, ``correct`` and every
number compared with its limit.  The benchmark's own runs never run this.
The faults also back the CPU tests in ``tests/bench``.
"""

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import repro.core.dataplane as dataplane  # noqa: E402
import repro.models.moe as moe  # noqa: E402
import repro.models.registry as registry  # noqa: E402
import repro.train.step as train_step  # noqa: E402
from bench import harness  # noqa: E402


# -- faults ----------------------------------------------------------------------

def exchange_left_out(setattr):
    """Every chip keeps its own buffers: nothing crosses between chips."""
    setattr(dataplane.NimbleAllToAll, "execute", lambda self, x, chunks: x)


def a2av_answer_altered(setattr):
    """One element of each delivered buffer is changed where it is made."""
    execute = dataplane.NimbleAllToAll.execute

    def altered(self, x, chunks):
        return execute(self, x, chunks).at[1, 0, 0].add(1.0)

    setattr(dataplane.NimbleAllToAll, "execute", altered)


def a2av_half_left_out(setattr):
    """Half of every source's live chunks are planned and sent."""
    plan = dataplane.NimbleAllToAll.plan_from_counts
    setattr(dataplane.NimbleAllToAll, "plan_from_counts",
            lambda self, c: plan(self, c // 2))


def ffn_answer_altered(setattr):
    """One row of the expert FFN's output is changed where it is made."""
    ffn = moe.grouped_ffn
    setattr(moe, "grouped_ffn",
            lambda x, *a, **k: ffn(x, *a, **k).at[0].add(1.0))


def ffn_half_left_out(setattr):
    """The expert FFN computes the first half of its rows; the rest read 0."""
    ffn = moe.grouped_ffn

    def half(x, eid, *a, **k):
        keep = jnp.arange(x.shape[0]) < x.shape[0] // 2
        return ffn(x, jnp.where(keep, eid, -1), *a, **k)

    setattr(moe, "grouped_ffn", half)


def moe_state_unchanged(setattr):
    """The expert layer returns its input."""
    make = moe.make_moe_ffn

    def make_identity(cfg, ctx):
        apply = make(cfg, ctx)
        return lambda p, x: (x, apply(p, x)[1])

    setattr(moe, "make_moe_ffn", make_identity)


def train_state_unchanged(setattr):
    """The step computes its loss and returns the state it was given."""
    make = train_step.make_train_step

    def make_frozen(model, opt_cfg, **kw):
        step = make(model, opt_cfg, **kw)

        def frozen(params, opt, batch):
            _, _, metrics = step(params, opt, batch)
            return params, opt, metrics
        return frozen

    setattr(train_step, "make_train_step", make_frozen)


def train_half_batch(setattr):
    """The loss is the mean over the first half of the positions only."""
    loss = registry.Model.loss

    def half(self, params, batch, **kw):
        s = batch["tokens"].shape[1] // 2
        return loss(self, params, {k: v[:, :s] for k, v in batch.items()},
                    **kw)

    setattr(registry.Model, "loss", half)


FAULTS = {
    "exchange_left_out": exchange_left_out,
    "a2av_answer_altered": a2av_answer_altered,
    "a2av_half_left_out": a2av_half_left_out,
    "ffn_answer_altered": ffn_answer_altered,
    "ffn_half_left_out": ffn_half_left_out,
    "moe_state_unchanged": moe_state_unchanged,
    "train_state_unchanged": train_state_unchanged,
    "train_half_batch": train_half_batch,
}


# -- controls: the reference, one precision down, in the program's place --------

def a2av_control(d):
    """The reference moves the payload as bfloat16."""
    ref = d.cell.reference()
    d.fn = jax.jit(lambda x, c: ref.all_to_allv(x, c, d.n, jnp.bfloat16),
                   out_shardings=(d.shard, d.shard))


def moe_control(d):
    """The reference computes the block with three-pass bf16 matmuls."""
    ref = d.cell.reference()
    d.fn = jax.jit(lambda p, x: ref.moe_block(p, x, d.k, ref.dot_high),
                   out_shardings=d.tok_sh)


def train_control(d):
    """The reference's first steps with three-pass bf16 matmuls stand in for
    the program's."""
    ref = d.cell.reference()
    batches = [(b["tokens"][0], b["labels"][0])
               for b in d.batches[:len(d.losses)]]
    d.losses, d.grad_norms, p3 = ref.adamw_steps(d.init_params(), batches,
                                                 d.c, ref.mm_high)
    d.change_norms = {k: float(v) for k, v in jax.jit(ref.leaf_norms)(
        jax.tree.map(jnp.subtract, p3, d.init_params())).items()}


CONTROLS = {"a2av": a2av_control, "moe_fwd": moe_control,
            "train": train_control}


class Patched:
    """``with Patched(fault):`` plants a fault and takes it out again."""

    def __init__(self, fault):
        self.fault, self.saved = fault, []

    def _set(self, obj, name, value):
        self.saved.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    def __enter__(self):
        self.fault(self._set)
        return self

    def __exit__(self, *exc):
        for obj, name, value in reversed(self.saved):
            setattr(obj, name, value)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--faults", nargs="*", default=[], choices=sorted(FAULTS))
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    cell = harness.find_cell(args.workload)
    devices = harness.find_chips(cell.chips)
    control = CONTROLS[cell.config["runner"]]
    runs = ([("program", s, None, None) for s in args.seeds]
            + [("control", s, None, control) for s in args.control_seeds]
            + [(f, s, FAULTS[f], None) for f in args.faults
               for s in args.fault_seeds])
    for what, seed, fault, after in runs:
        t = time.perf_counter()
        with Patched(fault or (lambda _set: None)):
            res = harness.run(cell, seed, args.seconds, False, t, devices,
                              after_setup=after)
        print(json.dumps({"reading": what, "seed": seed,
                          "correct": res["correct"], "checks": res["checks"],
                          "attempted": res["attempted"],
                          "s": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
