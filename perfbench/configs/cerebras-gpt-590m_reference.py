"""The plain reference of `cerebras-gpt-590m` (Cerebras-GPT, arXiv:2304.03208, Table 1,
row 590M): the `gpt_dense` family's, whole; the sizes come from the
configuration file beside this one."""
from perfbench.families.gpt_dense_reference import *  # noqa: F401,F403
