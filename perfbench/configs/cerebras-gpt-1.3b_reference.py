"""The plain reference of `cerebras-gpt-1.3b` (Cerebras-GPT, arXiv:2304.03208, Table 1,
row 1.3B): the `gpt_dense` family's, whole; the sizes come from the
configuration file beside this one."""
from perfbench.families.gpt_dense_reference import *  # noqa: F401,F403
