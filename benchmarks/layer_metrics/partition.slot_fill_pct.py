"""State: the key slots a partition on the keyed step has handed out over
the slots it states (`@capacity(keys=...)`), the fullest partition's. Slots
are never given back, so the reading at the window's end is the high water.
Source: `statistics_report()["partitions"][<partition>]`: `keys` (the
device table's count, synced at each report) over `capacity`. A program
without that section leaves nothing to read."""


def read(run: dict):
    partitions = run["stats1"].get("partitions")
    if not partitions:
        return None
    return max(100.0 * p["keys"] / p["capacity"]
               for p in partitions.values())
