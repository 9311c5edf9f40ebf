"""State: the rows a table on the device index has taken over the rows it
states (`@capacity(rows=...)`), the fullest table's. Rows are never given
back on the index, so the reading at the window's end is the high water.
Source: `statistics_report()["tables"][<table>]`: `rows` (the index's
count, synced at each report) over `capacity`. A program without that
section leaves nothing to read."""


def read(run: dict):
    tables = run["stats1"].get("tables")
    if not tables:
        return None
    return max(100.0 * t["rows"] / t["capacity"] for t in tables.values())
