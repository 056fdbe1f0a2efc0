"""What only a Ling hybrid configuration reads of its layers (delta-rule linear
attention, Kimi Delta Attention, whose state is a float32 MATRIX a head and
slot, beside ONE latent pool layer in six; one routing group of 512
sigmoid-routed experts held; ``families/ling_hybrid.py``) under a closed-loop
mix (suffix ``.batch``), from the program's trace session (``--trace 2``).  The
arithmetic is the family's ``layer_readings`` (``_family.py`` calls it once a
run); what other families have too (``mla_device_share``, ``mla_prefill_roofline``,
``experts_load_imbalance``, ``step_hbm_roofline_share``) is declared by
``family_serve_batch.py``.

- ``kda_state_gb_per_step.batch``: ``kda_state_bytes_rw`` / ``decode_steps``, what
  a decode step reads and writes of the delta-rule layers' matrix states: every
  slot's, idle or not (the kernel moves them all), six layers';
- ``kda_device_share.batch``: of the device time of the ops inside the traced
  decode and prefill programs, the share of the delta-rule mixers' (the two
  kernels by name, their projections, convolution, norms and gates by the
  family's table of shapes: the chip's events carry no scope), to be read
  against ``mla_device_share.batch``;
- ``kda_step_roofline.batch``: twice the state ONE ``kda_step`` call touches over
  the HBM rate, against the mean device time of the ``kda_step`` events inside
  the traced decode programs; memory-bound;
- ``kda_chunk_roofline.batch``: the ``kda_chunk`` events inside the traced prefill
  programs against the LARGER of the six mixers' operations over the MXU peak
  and must-move bytes over the HBM rate (the family's counts at each launch's
  rung: the operations as the mathematics has them, a triangular solve by
  substitution, not the kernel's products for an inverse);
- ``held_group_row_share.batch``: ``route_rows_held_group`` over the active rows
  of the decode steps' expert layers (``moe_assignments`` / experts a token): the
  rows whose four kept routing groups include the one held here (50% under an
  even router).

A run of another family, of a program without these counters or without
numbered launches, or without a session (any untraced run; a CPU run) leaves
every metric out.
"""

import os

from benchmark.layer_metrics import _family

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MOVES = "serve_tokens_per_s"
KDA = "Delta-rule mixer"
METRICS = {
    "kda_state_gb_per_step.batch": {"unit": "GB", "layer": "Hybrid cache", "moves": MOVES},
    "kda_device_share.batch": {"unit": "%", "layer": KDA, "moves": MOVES},
    "kda_step_roofline.batch": {"unit": "%", "layer": KDA, "moves": MOVES},
    "kda_chunk_roofline.batch": {"unit": "%", "layer": KDA, "moves": MOVES},
    "held_group_row_share.batch": {"unit": "%", "layer": "Expert layer", "moves": MOVES},
}


def read(run):
    return _family.pick(run, METRICS, ROOT)
