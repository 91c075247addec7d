"""The plain reference of `granite-4.0-h-small`
(https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json,
`model_type` `granitemoehybrid`): the `granite_hybrid` family's, whose
docstring writes the equations out, with this configuration's constants
bound from the file beside this one.

Departures from the published model, each also in the configuration file:
- depth: the first `num_hidden_layers` (10) entries of `layer_types`, one
  whole period of 9 Mamba-2 layers and 1 attention layer (`reduced`);
- experts: the router scores all 72 experts
  (`deployment.num_local_experts_published`) and picks its top 10 among
  them, and only the `num_local_experts` (36) experts held, from
  `deployment.experts_held_first` on, add to the sum; what the absent
  experts would add is left out, here as in the program (`reduced`; the
  stated deployment puts them on a second chip);
- Hugging Face stores an expert's gate and up matrices fused in one
  `input_linear`; here they are two leaves. `time_step_limit` is (0, inf),
  its default, so `dt` is not clamped (`assumed`);
- the weights are random from the seed, in bfloat16 (`assumed`).
"""
from __future__ import annotations

from pathlib import Path

from perfbench.families.granite_hybrid_reference import (
    bound_logits_at,
    layer as block,  # noqa: F401  one layer, as the family writes it
)

logits_at = bound_logits_at(
    Path(__file__).with_name("granite-4.0-h-small.json"))


def train_steps(*_args, **_kw):
    """No training cell: at 16 bytes a parameter one period of this
    model at the floors is 31 GB (ISSUE 28). The serving comparison is
    `logits_at`; a training reference comes with a training cell."""
    raise NotImplementedError("granite-4.0-h-small has no training cell: "
                              "it is served, not trained, on one chip")
