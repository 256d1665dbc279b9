"""Answers of the window served from the column cache (``source ==
"cache"``), as a share of the answers, in %."""


def read(ctx):
    share = ctx.facts.get("cache_hit_share")
    return None if share is None else share * 100.0
