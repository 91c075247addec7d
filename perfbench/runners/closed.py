"""Traffic of kind `closed`: the timed window drives
`ModelServer.generate(..., on_token=...)` in-process, and a closed loop of
clients keeps every slot of the engine full (`harness/serve_cell.py` holds
what the two serving kinds share)."""
from perfbench.harness.serve_cell import run  # noqa: F401
