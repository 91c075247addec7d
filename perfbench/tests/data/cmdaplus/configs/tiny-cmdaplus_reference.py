"""The `cohere2_moe` family's reference bound to the toy beside it."""
from pathlib import Path

from perfbench.families.cohere2_moe_reference import bound_logits_at

logits_at = bound_logits_at(Path(__file__).with_name("tiny-cmdaplus.json"))
