"""The `deepseek_v2` family's reference bound to the toy beside it."""
from pathlib import Path

from perfbench.families.deepseek_v2_reference import bound_logits_at

logits_at = bound_logits_at(Path(__file__).with_name("tiny-dsv2.json"))
