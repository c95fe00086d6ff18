"""Mean number of shards a tier request was routed to: the ``shards`` arg
of the program's ``repro.tier.assign`` spans. Layer: router
(``serve/router.py``, ``serve/shard.py`` ``ShardMap.window_shards``).
Moves ``assign_points_per_s``."""

import statistics


def read(run):
    spans = run.trace.program_named("tier.assign") if run.trace else []
    shards = [s.args["shards"] for s in spans if "shards" in s.args]
    return statistics.fmean(shards) if shards else None
