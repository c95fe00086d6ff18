"""Drivers, one per kind of operation a traffic file names in ``op``.

Each module provides ``setup(ctx)`` (data, build, warm-up: all set-up),
``window(ctx, state)`` (the measured loop), ``end_to_end(result)``,
``release(state)`` (drop the program's device state) and
``check(ctx, state, result)`` (compare with the plain reference).
"""
