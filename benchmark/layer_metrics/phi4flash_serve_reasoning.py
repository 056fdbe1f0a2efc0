"""What only a Phi-4-flash configuration reads of its layers (a decoder whose
second half keeps no cache: ONE pool layer that eight layers read, gated memory
units that read one layer's scan output, Mamba-1 mixers beside rings under
differential attention; ``families/phi4flash.py``) under a closed-loop mix
(suffix ``.batch``), from the program's trace session (``--trace 2``).  The
arithmetic is the family's ``layer_readings`` (``_family.py`` calls it once a
run); what other families have too (``ring_gb_per_step``, ``ring_decode_roofline``,
``window_flash_roofline``, ``swa_window_read_share``, ``ssm_state_gb_per_step``,
``ssm_step_roofline``, ``ssm_device_share``, ``attn_device_share``,
``step_hbm_roofline_share``) is declared by ``family_serve_batch.py`` and
``laguna_serve_mixedlen.py``.

- ``shared_pool_gb_per_step.batch``: ``shared_pool_bytes_read`` / ``decode_steps``,
  what a decode step reads of the one pool layer, ALL its readers (layer 17 and
  the seven cross-attention layers) counted;
- ``shared_pool_decode_roofline.batch``: those bytes over the HBM rate, against
  the device time a traced decode program spends in the ``paged_decode`` events
  whose operands hold the pool (the eight readings of a step); memory-bound;
- ``s6_scan_roofline.batch``: the ``selective_scan`` kernel's events inside the
  traced prefill programs against the LARGER of the nine Mamba-1 layers'
  must-move bytes over the HBM rate and their operations over the MXU peak (the
  family's counts at each launch's rung; the operations run on the vector unit,
  whose peak is lower, so the bytes decide);
- ``gmu_device_share.batch`` / ``diffattn_combine_device_share.batch``: of the
  device time of the ops inside the traced decode and prefill programs, the
  share of the gated memory units' and of differential attention's combination
  (lambda, the subtraction, the sub-norm: what ``vs.diff-attn`` holds), by the
  family's table of shapes (the chip's events carry no scope);
- ``prefill_cross_rows_share.batch``: ``prefill_rows_cross`` /
  ``prefill_tokens_real``, of the real prompt rows the first half of the stack
  ran, the share the second half ran too: one a prompt (1 the day a change runs
  the second half over a prompt again).

A run of another family, of a program without these counters or without
numbered launches, or without a session (any untraced run; a CPU run) leaves
every metric out.
"""

import os

from benchmark.layer_metrics import _family

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MOVES = "serve_tokens_per_s"
POOL = "Shared K/V pool"
METRICS = {
    "shared_pool_gb_per_step.batch": {"unit": "GB", "layer": POOL, "moves": MOVES},
    "shared_pool_decode_roofline.batch": {"unit": "%", "layer": POOL, "moves": MOVES},
    "s6_scan_roofline.batch": {"unit": "%", "layer": "State-space mixer", "moves": MOVES},
    "gmu_device_share.batch": {"unit": "%", "layer": "Gated memory unit", "moves": MOVES},
    "diffattn_combine_device_share.batch": {"unit": "%", "layer": "Attention", "moves": MOVES},
    "prefill_cross_rows_share.batch": {"unit": "ratio", "layer": "Serve engine", "moves": MOVES},
}


def read(run):
    return _family.pick(run, METRICS, ROOT)
