"""Traffic of kind `open`: the timed window drives
`ModelServer.generate(..., on_token=...)` in-process, and requests arrive on
a schedule, whatever the engine has finished (`harness/serve_cell.py` holds
what the two serving kinds share)."""
from perfbench.harness.serve_cell import run  # noqa: F401
