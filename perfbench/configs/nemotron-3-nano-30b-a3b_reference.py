"""The plain reference of `nemotron-3-nano-30b-a3b`
(https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json,
`model_type` `nemotron_h`): the `nemotron_h` family's, whose docstring
writes the equations out, with this configuration's constants bound from
the file beside this one.

Departures from the published model, each also in the configuration file:
- depth: the first `num_hidden_layers` (16) characters of
  `hybrid_override_pattern`, `MEMEM*EMEMEM*EME`: 7 Mamba-2, 7 expert and
  2 attention layers of the published 23 / 23 / 6 (`reduced`; the stated
  deployment puts layers 16-51 on three further pipeline stages). The
  final norm and the head follow layer 15 here, so that there are logits
  to compare;
- experts: the router scores all 128 experts
  (`deployment.n_routed_experts_published`) and picks its top 6 among
  them, and only the `n_routed_experts` (64) experts held, from
  `deployment.experts_held_first` on, add to the sum; what the absent
  experts would add is left out, here as in the program (`reduced`; the
  stated deployment puts them on a second chip);
- no rotary in the attention layers, `dt` unclamped, a float32 state and
  correction bias, the fused projection leaves (`assumed`);
- the weights are random from the seed, in bfloat16 (`assumed`).
"""
from __future__ import annotations

from pathlib import Path

from perfbench.families.nemotron_h_reference import (
    bound_logits_at,
    layer as block,  # noqa: F401  one layer, as the family writes it
)

logits_at = bound_logits_at(
    Path(__file__).with_name("nemotron-3-nano-30b-a3b.json"))


def train_steps(*_args, **_kw):
    """No training cell: at 16 bytes a parameter one expert layer of this
    model needs 16 chips by the driver's count (ISSUE 36). The serving
    comparison is `logits_at`; a training reference comes with a training
    cell."""
    raise NotImplementedError("nemotron-3-nano-30b-a3b has no training "
                              "cell: it is served, not trained, on one chip")
