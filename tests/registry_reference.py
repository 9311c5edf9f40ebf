"""Plain per-event reference for a primary-key table written by a change
stream and probed by a join: the SiddhiQL guide's `update or insert` and
`Join (Table)` over a device registry (a dict, no numpy, nothing of the
program). A copy of the per-event core of `benchmarks/references/
registry_1m.py`; `benchmarks/tests/test_registry.py` holds the two to each
other, and tests/test_table_index.py holds the engine to this one."""

WANTED = "server-room"


class Registry:
    """A primary-key table of rows `(roomNo, type, updated)` by deviceID,
    one event per turn."""

    def __init__(self) -> None:
        self.rows: dict = {}
        self.duplicates = 0

    def upsert(self, device_id, room, kind, stamp) -> None:
        """`update or insert`: the event's row, whatever was there."""
        self.rows[device_id] = (room, kind, stamp)

    def upsert_many(self, device_ids, rows) -> None:
        """`upsert` of each (device_id, (room, kind, stamp)) in turn."""
        self.rows.update(zip(device_ids, rows))

    def insert(self, device_id, room, kind, stamp) -> None:
        """`insert into` a primary-key table: a key it holds is refused
        and counted."""
        if device_id in self.rows:
            self.duplicates += 1
        else:
            self.rows[device_id] = (room, kind, stamp)

    def enrich(self, device_id, temp, stamp, wanted=WANTED):
        """The row a reading gives, or None: an inner join on the key and
        `having roomType == wanted`."""
        row = self.rows.get(device_id)
        if row is None or row[1] != wanted:
            return None
        room, kind, updated = row
        return (device_id, room, kind, temp, stamp, updated)
