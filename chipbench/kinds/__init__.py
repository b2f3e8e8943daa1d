"""Runners, one module per ``kind`` of traffic file: ``run(ctx)`` warms
up, checks correctness, measures for ``ctx["seconds"]`` and returns the
observations the result line and the per-layer readers are made from."""
