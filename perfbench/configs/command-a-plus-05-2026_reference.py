"""The plain reference of `command-a-plus-05-2026`
(https://huggingface.co/CohereLabs/command-a-plus-05-2026/blob/main/config.json):
the `cohere2_moe` family's, whose docstring writes the equations out,
with this configuration's constants bound from the file beside this one.

Departures from the published model, each also in the configuration file:
- depth: the first `num_hidden_layers` (4) of the published 32 layers:
  one whole period of `layer_switch` 4 (three sliding-window layers with
  rotary, then one full-attention layer without positions;
  `first_k_dense_replace` 0: no leading dense layer) (`reduced`; the
  stated deployment puts layers 4-31 on seven further pipeline stages).
  The final norm and the tied head follow layer 3 here, so that there
  are logits to compare;
- experts: the router scores all 128 experts
  (`deployment.num_experts_published`) and picks each token's 8, and only
  the `num_experts` (16) experts held, from
  `deployment.experts_held_first` on, add to the sum; what the other 112
  would add is left out, here as in the program (`reduced`; the stated
  deployment puts them on 7 further chips). The four shared experts'
  part is whole: every chip computes it for its own tokens;
- vocabulary: ids 0-32767 of 262144, the tied embedding's rows as
  embedding and as head (`reduced`: the chip's slice of eight);
- "average" read as the MEAN of the four shared experts' outputs; the
  window counting the query's own position; interleaved rotary over all
  128 dimensions on the sliding layers and NO position on the full ones;
  no router bias; `intermediate_size` as one expert's width; float32
  router, softmax and norm statistics; no vision tower (`assumed`);
- the weights are random from the seed, in bfloat16, the attention's
  drawn so that it is a material part of the stream (`assumed`).
"""
from __future__ import annotations

from pathlib import Path

from perfbench.families.cohere2_moe_reference import (
    bound_logits_at,
    layer as block,  # noqa: F401  one layer, as the family writes it
)

logits_at = bound_logits_at(
    Path(__file__).with_name("command-a-plus-05-2026.json"))


def train_steps(*_args, **_kw):
    """No training cell: at 16 bytes a parameter the least the floors
    allow of this model (75.7 GB) does not fit one chip (ISSUE 49), and
    the benchmark's one training metric belongs to a dense net. The
    serving comparison is `logits_at`; a training reference comes with a
    training cell."""
    raise NotImplementedError("command-a-plus-05-2026 has no training "
                              "cell: it is served, not trained, on one chip")
