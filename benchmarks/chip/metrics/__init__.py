"""One reader per metric: ``metrics/<name>.py`` defines ``read(run)``,
``run`` a ``record.RunRecord``; None means nothing to read."""
