"""The plain reference of the rehearsal's toy: the `olmo_hybrid`
family's, with the constants of the file beside this one."""
from pathlib import Path

from perfbench.families.olmo_hybrid_reference import bound_logits_at

logits_at = bound_logits_at(Path(__file__).with_name("tiny-olmo.json"))
