"""One reader per kind of source. A per-layer metric's file
(``layer_metrics/<metric>.json``) names its reader; a new reduction is a new
file here. ``read(spec, run)`` returns the metric's value, or ``None`` where
it finds nothing to read (the harness then leaves the metric out of the
line). ``run`` is the :class:`benchmark.run.RunData` of the run."""
