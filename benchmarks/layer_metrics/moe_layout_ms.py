"""`moe.layout_ms`: `program_scopes.py`'s milliseconds a round of the layout."""

from benchmarks.layer_metrics.program_scopes import ms_a_round as read  # noqa: F401
