"""The plain reference of `olmo-hybrid-7b`
(https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json,
`model_type` `olmo_hybrid`): the `olmo_hybrid` family's, whose docstring
writes the equations out, with this configuration's constants bound from
the file beside this one.

Departures from the published model, each also in the configuration file:
- depth: the first `num_hidden_layers` (16) entries of `layer_types`, four
  whole periods of 3 linear-attention layers and 1 full-attention layer
  (`reduced`; the stated deployment puts layers 16-31 on a second chip).
  The final norm and the head follow layer 15 here, so that there are
  logits to compare;
- what the config's keys do not state is set by the Olmo 2/3 family's
  convention (norm after each sub-layer, QK-norm over the whole
  projection, no rotary: `rope_theta` is null) or by the `fla` / Hugging
  Face gated-delta-net layer whose key names the config uses (convolution
  and silu on q, k, v; L2-normed q and k; the `A_log` / `dt_bias` decay;
  the output gate after its per-head norm): `assumed`;
- a linear layer's six projections are one fused leaf, a full layer's
  q, k, v another: the same products (`assumed`);
- the weights are random from the seed, in bfloat16 (`assumed`).
"""
from __future__ import annotations

from pathlib import Path

from perfbench.families.olmo_hybrid_reference import (
    bound_logits_at,
    layer as block,  # noqa: F401  one layer, as the family writes it
)

logits_at = bound_logits_at(
    Path(__file__).with_name("olmo-hybrid-7b.json"))


def train_steps(*_args, **_kw):
    """No training cell: at 16 bytes a parameter one period and an eighth
    of the vocabulary are 14.9 GB before any activation (ISSUE 32). The
    serving comparison is `logits_at`; a training reference comes with a
    training cell."""
    raise NotImplementedError("olmo-hybrid-7b has no training cell: it is "
                              "served, not trained, on one chip")
