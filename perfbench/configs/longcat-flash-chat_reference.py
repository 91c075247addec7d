"""The plain reference of `longcat-flash-chat`
(https://huggingface.co/meituan-longcat/LongCat-Flash-Chat/blob/main/config.json):
the `longcat_flash` family's, whose docstring writes the equations out,
with this configuration's constants bound from the file beside this one.

Departures from the published model, each also in the configuration file:
- depth: the first `num_layers` (4) of the published 28 layers, every one
  the same double layer (`reduced`; the stated deployment puts layers
  4-27 on six further pipeline stages). The final norm and the head
  follow layer 3 here, so that there are logits to compare;
- experts: the router scores all 512 real experts
  (`deployment.n_routed_experts_published`) and the 256 zero-compute ones
  and picks its top 12 among the 768, and only the `n_routed_experts`
  (16) real experts held, from `deployment.experts_held_first` on, add
  to the sum; what the absent experts would add is left out, here as in
  the program (`reduced`; the stated deployment puts them on 31 further
  chips). The zero-compute experts' identity part is whole: it is this
  chip's own tokens';
- vocabulary: ids 0-16383 of 131072, embedding rows and head columns
  alike (`reduced`: the chip's slice of eight);
- an untied head, unnormalised gates, the identity zero expert, the
  layer order, interleaved rotary without scaling, the softmax scale, a
  float32 router (`assumed`);
- the weights are random from the seed, in bfloat16 (`assumed`).
"""
from __future__ import annotations

from pathlib import Path

from perfbench.families.longcat_flash_reference import (
    bound_logits_at,
    layer as block,  # noqa: F401  one layer, as the family writes it
)

logits_at = bound_logits_at(
    Path(__file__).with_name("longcat-flash-chat.json"))


def train_steps(*_args, **_kw):
    """No training cell: at 16 bytes a parameter the least the floors
    allow of this model is 63 GB, four chips' worth (ISSUE 40). The
    serving comparison is `logits_at`; a training reference comes with a
    training cell."""
    raise NotImplementedError("longcat-flash-chat has no training cell: "
                              "it is served, not trained, on one chip")
