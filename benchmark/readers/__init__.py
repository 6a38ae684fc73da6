"""One small reader a kind of per-layer metric: ``read(run, trace, spec, kind)``
takes the metric from the run's spans and counters or from the reduced device
trace, with the parameters its ``metrics/<name>.json`` gives. A reader that
finds nothing to read returns None."""
