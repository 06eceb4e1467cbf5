"""Reader kinds: each module has ``read(params, evidence) -> float | None``.
A per-layer metric's file (``benchmark/layer_metrics/<metric>.json``) names
its reader and gives its parameters."""
