"""``bench/calibrate.py`` for the cells of the ``lm_fwd`` runner: the same
arguments and the same lines, with that runner's control (``Runner.control``:
the reference with its matmul inputs rounded to fp8's 3-bit mantissa in the
program's place) and two faults of its MoE layers added to calibrate's
tables.

    python bench/calibrate_lm.py --workload <cell> --seeds 1 2 ... \
        --control-seeds 1 2 3 --faults shared_left_out --fault-seeds 1
"""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import repro.models.moe as moe  # noqa: E402
from bench import calibrate  # noqa: E402


def shared_left_out(setattr):
    """The MoE layers add no shared experts."""
    setattr(moe, "_shared_ffn", lambda p, xf: jnp.zeros_like(xf))


def bias_in_gate_weights(setattr):
    """The sigmoid router weights the chosen experts by score + bias."""
    def topk(p, logits, cfg):
        s = jax.nn.sigmoid(logits) + p["router_bias"]
        _, idx = jax.lax.top_k(s, cfg.top_k)
        w = jnp.take_along_axis(s, idx, -1)
        w = w / (w.sum(-1, keepdims=True) + 1e-20) * cfg.routed_scale
        return idx.astype(jnp.int32), w, jnp.float32(0.0)

    setattr(moe, "_sigmoid_topk", topk)


calibrate.FAULTS.update(shared_left_out=shared_left_out,
                        bias_in_gate_weights=bias_in_gate_weights)
calibrate.CONTROLS.setdefault("lm_fwd", lambda runner: runner.control())

if __name__ == "__main__":
    sys.exit(calibrate.main())
