"""The plain reference of `ling-3.0-flash`
(https://huggingface.co/inclusionAI/Ling-3.0-flash/blob/main/config.json):
the `ling_flash` family's, whose docstring writes the equations out,
with this configuration's constants bound from the file beside this one.

Departures from the published model, each also in the configuration file:
- depth: the first `num_hidden_layers` (12) of the published 42 layers:
  two whole periods of `layer_group_size` 6 (five KDA layers, then one
  MLA layer, twice), the two leading dense layers and the ten routed
  layers after them (`reduced`; the stated deployment puts layers 12-41
  on three further pipeline stages). The final norm and the head follow
  layer 11 here, so that there are logits to compare;
- experts: the router scores all 512 experts
  (`deployment.num_experts_published`), keeps each token's 4 best of the
  8 groups and picks its 8 among them, and only the `num_experts` (64)
  experts held, group 0 from `deployment.experts_held_first` on, add to
  the sum; what the other seven groups' experts would add is left out,
  here as in the program (`reduced`; the stated deployment puts them on 7
  further chips). The shared expert's part is whole: every chip computes
  it for its own tokens;
- vocabulary: ids 0-19647 of 157184, embedding rows and head columns
  alike (`reduced`: the chip's slice of eight);
- the layer rule `(l + 1) % layer_group_size == 0`; KDA as
  arXiv:2510.26692 / `fla` write it (convolution and silu on q, k, v;
  L2-normed q and k; `A` a head and the bias a channel under the safe
  gate `-5 sigmoid(exp(A)(f + bias))`; a sigmoid output gate a channel
  at full rank; an RMSNorm a head); in the MLA layers `use_qk_norm` as
  the latent's RMSNorm only, full-rank queries, the head-wise gate a
  sigmoid on the heads' outputs before `W_o`, interleaved rotary pairs,
  scale 192^-1/2; a group's score the sum of its two largest biased
  scores (`noaux_tc`), what lies outside the kept groups set to -inf;
  float32 router, scores, bias and state; no clamp in the layers held;
  no multi-token-prediction module (`assumed`);
- the weights are random from the seed, in bfloat16, the correction bias
  in float32 and large enough to move choices (`assumed`).
"""
from __future__ import annotations

from pathlib import Path

from perfbench.families.ling_flash_reference import (
    bound_logits_at,
    layer as block,  # noqa: F401  one layer, as the family writes it
)

logits_at = bound_logits_at(Path(__file__).with_name("ling-3.0-flash.json"))


def train_steps(*_args, **_kw):
    """No training cell: at 16 bytes a parameter the least the floors
    allow of this model does not fit one chip (ISSUE 46), and the
    benchmark's one training metric belongs to a dense net. The serving
    comparison is `logits_at`; a training reference comes with a
    training cell."""
    raise NotImplementedError("ling-3.0-flash has no training cell: it is "
                              "served, not trained, on one chip")
