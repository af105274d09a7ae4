"""`swa.attention_round_share_pct`: `kda.round_share_pct`'s reader over both
kinds of flash call, by the name pattern in the metric's file."""

from benchmarks.layer_metrics.kda_round_share_pct import read  # noqa: F401
