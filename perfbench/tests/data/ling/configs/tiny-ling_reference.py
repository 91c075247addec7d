"""The `ling_flash` family's reference bound to the toy beside it."""
from pathlib import Path

from perfbench.families.ling_flash_reference import bound_logits_at

logits_at = bound_logits_at(Path(__file__).with_name("tiny-ling.json"))
