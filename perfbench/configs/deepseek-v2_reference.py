"""The plain reference of `deepseek-v2`
(https://huggingface.co/deepseek-ai/DeepSeek-V2/blob/main/config.json):
the `deepseek_v2` family's, whose docstring writes the equations out,
with this configuration's constants bound from the file beside this one.

Departures from the published model, each also in the configuration file:
- depth: the first `num_hidden_layers` (5) of the published 60 layers:
  the leading dense layer and the four routed layers after it
  (`reduced`; the stated deployment puts layers 5-59 on eleven further
  pipeline stages). The final norm and the head follow layer 4 here, so
  that there are logits to compare;
- experts: the router scores all 160 experts
  (`deployment.n_routed_experts_published`), keeps each token's 3 best of
  the 8 groups and picks its 6 among them, and only the
  `n_routed_experts` (20) experts held, group 0 from
  `deployment.experts_held_first` on, add to the sum; what the other
  seven groups' experts would add is left out, here as in the program
  (`reduced`; the stated deployment puts them on 7 further chips). The
  shared experts' part is whole: every chip computes it for its own
  tokens;
- vocabulary: ids 0-12799 of 102400, embedding rows and head columns
  alike (`reduced`: the chip's slice of eight);
- interleaved rotary under YaRN, the softmax scale, a float32 router, the
  group's score its largest, no correction bias, the shared experts as
  one MLP (`assumed`);
- the weights are random from the seed, in bfloat16 (`assumed`).
"""
from __future__ import annotations

from pathlib import Path

from perfbench.families.deepseek_v2_reference import (
    bound_logits_at,
    layer as block,  # noqa: F401  one layer, as the family writes it
)

logits_at = bound_logits_at(Path(__file__).with_name("deepseek-v2.json"))


def train_steps(*_args, **_kw):
    """No training cell: at 16 bytes a parameter the least the floors
    allow of this model is 32 GB, two chips' worth (ISSUE 42). The
    serving comparison is `logits_at`; a training reference comes with a
    training cell."""
    raise NotImplementedError("deepseek-v2 has no training cell: it is "
                              "served, not trained, on one chip")
