"""One small reader a file: ``read(context, **args)`` takes a per-layer
metric from what a traced run left in ``context`` (see ``measure.Result``)
and returns a number, or None where there is nothing to read; ``run.py``
then leaves the metric out of the line. ``metrics/<name>.json`` names the
reader and gives its arguments."""
