"""Compile set-up: share of cacheable compile requests the persistent cache
answered. 100 on every run of a cell but the first in a checkout."""


def read(run: dict):
    cache = run["cache"]
    if not cache["requests"]:
        return None
    return 100.0 * cache["hits"] / cache["requests"]
