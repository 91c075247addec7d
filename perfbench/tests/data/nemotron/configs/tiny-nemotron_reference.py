"""The `nemotron_h` family's reference bound to the toy beside it."""
from pathlib import Path

from perfbench.families.nemotron_h_reference import bound_logits_at

logits_at = bound_logits_at(Path(__file__).with_name("tiny-nemotron.json"))
