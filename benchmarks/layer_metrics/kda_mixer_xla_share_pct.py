"""`kda.mixer_xla_share_pct`: `program_scopes.py`'s share of the KDA mixer."""

from benchmarks.layer_metrics.program_scopes import share_pct as read  # noqa: F401
