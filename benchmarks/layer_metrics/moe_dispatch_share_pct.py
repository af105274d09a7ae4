"""`moe.dispatch_share_pct`: `program_scopes.py`'s share of the expert dispatch."""

from benchmarks.layer_metrics.program_scopes import share_pct as read  # noqa: F401
