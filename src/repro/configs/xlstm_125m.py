"""xLSTM-125M [arXiv:2405.04517] — alternating sLSTM + mLSTM blocks.

Attention-free recurrence: NIMBLE inapplicable (balanced collectives only);
built without the technique per DESIGN.md §7.  Runs long_500k natively
(O(1) state decode).
"""
from .base import ModelConfig, register

register(ModelConfig(
    name="xlstm-125m",
    arch_type="ssm",
    n_layers=12,
    d_model=768,
    n_heads=4,
    n_kv_heads=4,
    d_ff=0,                    # blocks carry their own projection factors
    vocab=50304,
    ssm_state=64,
    ssm_heads=4,
    slstm_every=2,             # even layers sLSTM, odd mLSTM
    # chunkwise-parallel mLSTM + associative-scan sLSTM (the per-step scans
    # stay selectable via mlstm_chunk=0 / slstm_assoc=False)
    mlstm_chunk=64,
    slstm_assoc=True,
))
